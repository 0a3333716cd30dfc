"""experts: distinct experts with at least one row per layer of a decode run (of num_experts), from the deltas of stats()["moe"] over the window: experts_hit / layer_runs."""
from benchmark.harness import moe_phases, phases


@phases.quiet
def read(ctx):
    r = moe_phases.routing(ctx)
    return r["experts_hit"] / r["layers"] if r else None
