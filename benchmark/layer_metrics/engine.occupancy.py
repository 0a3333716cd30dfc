"""engine (kv_cache.py): tokens generated per step over max_batch."""
from benchmark.harness import readers


def read(ctx):
    return readers.engine_occupancy_pct(ctx)
