"""engine: window seconds per engine step (stats() steps delta)."""
from benchmark.harness import readers


def read(ctx):
    return readers.engine_step_ms(ctx)
