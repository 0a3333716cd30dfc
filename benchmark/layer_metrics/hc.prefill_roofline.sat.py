"""residual path: over the capture's prefill runs, the least time the chip could take for each run's steps 1-4 at its BUCKET's length (benchmark/harness/hc_flops.py: one read of the streams for the flattened norm and the projection, one for u, one read and one write for the write-back, y and u once, Phi once; the FLOPs of r Phi and the mixes: the bytes bound it), summed, over hc.prefill_ms.sat's time, summed: the share a fused kernel would be held to."""
from benchmark.harness import hc_phases, phases


@phases.quiet
def read(ctx):
    r = hc_phases.prefill_roofline(ctx)
    return r["pct"] if r else None
