"""engine: per engine step over the window, wall time less the engine thread's CPU time outside the two .fetch leaves: time the loop neither computed nor waited for the chip (the GIL, a lock); by leaf and for llm.other in the info line."""
from benchmark.harness import phases, train_gaps


@phases.quiet
def read(ctx):
    return train_gaps.engine_offcpu_ms(ctx)
