"""model step, serving: device time per decode run (a jit_fwd run that starts inside an llm.decode annotation) of the attention over the paged cache: the operations under scope kv.attend plus the kernel paged_decode, all layers."""
from benchmark.harness import attend_phases, phases


@phases.quiet
def read(ctx):
    return (attend_phases.capture(ctx) or {}).get("attend_ms")
