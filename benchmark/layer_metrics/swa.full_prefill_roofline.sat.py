"""full layer's prefill attention: the same over the operations under attn.full: the causal triangle, t (t + 1) / 2 pairs, of the one layer that keeps every position."""
from benchmark.harness import phases, swa_phases


@phases.quiet
def read(ctx):
    r = swa_phases.full_prefill_roofline(ctx)
    return r["pct"] if r else None
