"""engine: device idle time per llm.step in the capture under every leaf annotation but .run / .fetch (sampling, scheduling, packing)."""
from benchmark.harness import phases


@phases.quiet
def read(ctx):
    return (phases.serve_capture(ctx) or {}).get("idle_host_ms")
