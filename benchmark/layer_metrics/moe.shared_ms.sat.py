"""experts: device time per decode run of the operations under the scope moe.shared (the shared expert: a dense gated MLP every token passes through), all layers."""
from benchmark.harness import phases, ssm_phases


@phases.quiet
def read(ctx):
    return (ssm_phases.capture(ctx) or {}).get("shared_ms")
