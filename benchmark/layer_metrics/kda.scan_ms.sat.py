"""delta-rule mixers: device time per prefill run (a jit_fwd run that starts inside an llm.prefill annotation) of the operations under the scope kda.scan (the chunked delta rule: decayed scores, the triangular solve, the state carried from chunk to chunk), all KDA layers; by bucket in the info line."""
from benchmark.harness import kda_phases, phases


@phases.quiet
def read(ctx):
    return (kda_phases.capture(ctx) or {}).get("scan_ms")
