"""step: device time per traced step of the operations under the scope loss (the tf_op stat of the event's metadata); every scope's time in the info line."""
from benchmark.harness import phases


@phases.quiet
def read(ctx):
    return phases.scope_ms(ctx, "loss_ms")
