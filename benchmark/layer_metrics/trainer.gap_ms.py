"""trainer: device idle time between two runs of the step program (last operation of run N to first operation of run N+1), runs paired with steps by the step tag of train.step.dispatch; median over the traced steps, the list by step and its parts in the info line."""
from benchmark.harness import phases, train_gaps


@phases.quiet
def read(ctx):
    return (train_gaps.tagged(ctx) or {}).get("gap_ms")
