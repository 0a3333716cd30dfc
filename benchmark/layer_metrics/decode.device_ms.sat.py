"""model step, serving: device time of the decode program per step, from the trace."""
from benchmark.harness import readers


def read(ctx):
    return readers.decode_device_ms(ctx)
