"""trainer (train/): median wall time of a traced step less the device's busy time per step."""
from benchmark.harness import readers


def read(ctx):
    return readers.trainer_host_ms(ctx)
