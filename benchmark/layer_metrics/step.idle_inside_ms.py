"""step: device idle time per traced step inside a run of the step program (first to last operation, no operation running): the compiler's and the kernels' holes; mean over the runs and the chips."""
from benchmark.harness import phases, train_gaps


@phases.quiet
def read(ctx):
    return (train_gaps.capture(ctx) or {}).get("inside_ms")
