"""trainer: the part of the gap between two runs from run N's last operation to the start of step N's train.report (paired by the step tag): how long after the device finished the loop had its loss and moved on; median over the traced steps."""
from benchmark.harness import phases, train_gaps


@phases.quiet
def read(ctx):
    return ((train_gaps.tagged(ctx) or {}).get("parts_ms") or {}).get("return")
