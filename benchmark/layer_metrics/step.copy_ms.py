"""step: device time per traced step of the operations whose opcode is copy (the sum of the capture's copy_ms_by_scope, which the info line holds by scope); 0.0 where a traced step holds none."""
from benchmark.harness import phases


@phases.quiet
def read(ctx):
    cap = phases.train_capture(ctx)
    return sum(cap["copy_ms_by_scope"].values()) if cap else None
