"""residual path: device time per prefill run (a jit_fwd run that starts inside an llm.prefill annotation) of the operations under hc.map, hc.pre and hc.post, all sublayers, the mean over the capture's prefill runs; by bucket in the info line (benchmark/harness/hc_phases.py)."""
from benchmark.harness import hc_phases, phases


@phases.quiet
def read(ctx):
    return (hc_phases.capture(ctx) or {}).get("prefill_ms")
