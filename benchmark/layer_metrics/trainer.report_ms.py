"""trainer: median train.report annotation per traced step (observe and push in the info line)."""
from benchmark.harness import phases


@phases.quiet
def read(ctx):
    return (phases.train_capture(ctx) or {}).get("report_ms")
