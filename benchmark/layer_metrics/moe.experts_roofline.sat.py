"""experts: the least time the chip could take for a decode run's expert matmuls (FLOPs of the pairs, bytes of the matrices of the experts HIT plus the rows in and out: benchmark/harness/moe_flops.py) over moe.experts_ms.sat."""
from benchmark.harness import moe_phases, phases


@phases.quiet
def read(ctx):
    r = moe_phases.experts_roofline(ctx)
    return r["pct"] if r else None
