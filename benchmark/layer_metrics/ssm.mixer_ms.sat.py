"""state-space mixers: device time per decode run (a jit_fwd run that starts inside an llm.decode annotation) of the operations under the scopes ssm.in_proj, ssm.conv, ssm.step, ssm.scan, ssm.gate_norm and ssm.out_proj, all state-space layers; each scope's share in the info line."""
from benchmark.harness import phases, ssm_phases


@phases.quiet
def read(ctx):
    return (ssm_phases.capture(ctx) or {}).get("mixer_ms")
