"""experts: device time per decode run of the operations under the scope moe.experts (the grouped expert matmuls and their activation), all layers; a decode run is a jit_fwd run that starts inside an llm.decode annotation."""
from benchmark.harness import moe_phases, phases


@phases.quiet
def read(ctx):
    return (moe_phases.capture(ctx) or {}).get("experts_ms")
