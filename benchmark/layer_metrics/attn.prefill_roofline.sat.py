"""K/V attention's prefill among its own rows: over the capture's prefill runs, the least time the chip could take for each run's causal triangle at its BUCKET's length (benchmark/harness/gdn_flops.py: 2 x 2 x T^2 / 2 x head_dim x heads FLOPs a layer against the peak, q, k, v and the output once against the bandwidth; the kernel's whole diagonal blocks and the bucket's padding are not counted), summed, over attn.prefill_ms.sat's time, summed."""
from benchmark.harness import gdn_phases, phases


@phases.quiet
def read(ctx):
    r = gdn_phases.prefill_attend_roofline(ctx)
    return r["pct"] if r else None
