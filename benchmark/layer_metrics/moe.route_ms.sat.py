"""experts: device time per decode run of the operations under moe.route, moe.dispatch and moe.combine (router, top-k, sort, gather, un-sort, weighted sum), all layers; each scope's share in the info line."""
from benchmark.harness import moe_phases, phases


@phases.quiet
def read(ctx):
    return (moe_phases.capture(ctx) or {}).get("route_ms")
