"""engine: device idle time per llm.step in the capture under the .run / .fetch annotations (launch latency, the logits on their way to the host)."""
from benchmark.harness import phases


@phases.quiet
def read(ctx):
    return (phases.serve_capture(ctx) or {}).get("idle_fetch_ms")
