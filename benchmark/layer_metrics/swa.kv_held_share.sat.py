"""K/V pool in two groups: positions the two groups hold (stats()["attention"] kv_rows_held: every position of the full layer, a ring of window positions of each window layer) over what one group that kept every position in every layer would, in percent."""
from benchmark.harness import phases, swa_phases


@phases.quiet
def read(ctx):
    return swa_phases.kv_held_share(ctx)
