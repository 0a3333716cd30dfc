"""step (train_step.py, parallel/): device busy time per traced step, from the trace."""
from benchmark.harness import readers


def read(ctx):
    return readers.step_device_ms(ctx)
