"""model step, serving: device time per jit_fwd run that starts inside an llm.prefill annotation; by bucket in the info line."""
from benchmark.harness import phases


@phases.quiet
def read(ctx):
    runs = (phases.serve_capture(ctx) or {}).get("prefill_ms")
    return sum(runs) / len(runs) if runs else None
