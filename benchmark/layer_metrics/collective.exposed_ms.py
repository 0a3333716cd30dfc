"""collectives (parallel/, ICI): collective time per step during which no other operation runs on that device, from the trace."""
from benchmark.harness import readers


def read(ctx):
    return readers.exposed_collective_ms(ctx)
