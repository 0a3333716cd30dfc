"""residual path: device time per decode run (a jit_fwd run that starts inside an llm.decode annotation) of the operations under the scope hc.map (models/xing.py: the RMS norm over a token's flattened streams, the projection through Phi, the sigmoids of H_pre and H_post, exp and the Sinkhorn's rounds of H_res), all sublayers (benchmark/harness/hc_phases.py); each scope's share in the info line."""
from benchmark.harness import hc_phases, phases


@phases.quiet
def read(ctx):
    return (hc_phases.capture(ctx) or {}).get("map_ms")
