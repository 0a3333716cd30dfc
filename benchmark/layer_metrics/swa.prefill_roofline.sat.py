"""window layers' prefill attention: over the capture's prefill runs, the least time the chip could take for each run's BAND at its bucket's length (benchmark/harness/swa_flops.py: 4 x heads x head_dim x sum_i min(i + 1, window) FLOPs a layer against the peak; q and the output at 128 heads, k and v at 8, once, against the bandwidth; the edge blocks' masked half and the bucket's padding are not counted), summed, over swa.prefill_ms.sat's time, summed."""
from benchmark.harness import phases, swa_phases


@phases.quiet
def read(ctx):
    r = swa_phases.prefill_roofline(ctx)
    return r["pct"] if r else None
