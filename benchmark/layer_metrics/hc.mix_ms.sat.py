"""residual path: device time per decode run of the operations under the scopes hc.pre (a sublayer's input u = sum_i H_pre[i] X_i read from the streams) and hc.post (its output written back: X' = H_res X + H_post y), all sublayers (benchmark/harness/hc_phases.py)."""
from benchmark.harness import hc_phases, phases


@phases.quiet
def read(ctx):
    return (hc_phases.capture(ctx) or {}).get("mix_ms")
