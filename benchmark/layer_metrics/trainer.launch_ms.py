"""trainer: the part of the gap between two runs from the start of step N+1's train.step.dispatch to run N+1's first operation: the call into the executable (dispatch, in the info line) plus the runtime's way to the chip; median over the traced steps."""
from benchmark.harness import phases, train_gaps


@phases.quiet
def read(ctx):
    return ((train_gaps.tagged(ctx) or {}).get("parts_ms") or {}).get("launch")
