"""state-space mixers: device time per prefill run (a jit_fwd run that starts inside an llm.prefill annotation) of the operations under the scope ssm.scan (the chunked form), all state-space layers; by bucket in the info line."""
from benchmark.harness import phases, ssm_phases


@phases.quiet
def read(ctx):
    return (ssm_phases.capture(ctx) or {}).get("scan_ms")
