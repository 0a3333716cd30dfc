"""engine: cancellations, admission, page allocation, packing, gauges and llm.other per engine step, from the deltas of stats()["phase_s"] over the window."""
from benchmark.harness import phases


@phases.quiet
def read(ctx):
    return (phases.engine_split_ms(ctx) or {}).get("schedule")
