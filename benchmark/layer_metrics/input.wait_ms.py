"""input: the program's own train.input.wait annotations per traced step (the loop's blocking dequeues, where the wait happens); train.input.transfer, on the prefetch thread, in the info line."""
from benchmark.harness import phases, train_gaps


@phases.quiet
def read(ctx):
    cap = train_gaps.capture(ctx)
    return cap["input_wait_ms"] if cap and cap["named"] else None
