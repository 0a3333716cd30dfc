"""engine: the host blocked on the device and on the logits per engine step (the .run and .fetch leaves of decode and prefill), from the deltas of stats()["phase_s"]."""
from benchmark.harness import phases


@phases.quiet
def read(ctx):
    return (phases.engine_split_ms(ctx) or {}).get("device_wait")
