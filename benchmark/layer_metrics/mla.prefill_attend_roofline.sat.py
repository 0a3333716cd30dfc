"""latent attention: over the capture's prefill runs, the least time the chip could take for each run's expansion matmuls and causal triangle at keys of 192 and values of 128 over its BUCKET's length (benchmark/harness/mla_flops.py: the mathematics' FLOPs; the kernel's padding of v and its whole diagonal blocks are not counted), summed, over mla.prefill_attend_ms.sat's time, summed."""
from benchmark.harness import mla_phases, phases


@phases.quiet
def read(ctx):
    r = mla_phases.prefill_attend_roofline(ctx)
    return r["pct"] if r else None
