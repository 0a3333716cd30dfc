"""engine: host sampling per engine step (llm.decode.sample + llm.prefill.sample), from the deltas of stats()["phase_s"] over the window."""
from benchmark.harness import phases


@phases.quiet
def read(ctx):
    return (phases.engine_split_ms(ctx) or {}).get("sample")
