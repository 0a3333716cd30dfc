"""step: FLOPs per step by the benchmark's convention over (device busy time per step x peak x chips)."""
from benchmark.harness import readers


def read(ctx):
    return readers.step_mfu_pct(ctx)
