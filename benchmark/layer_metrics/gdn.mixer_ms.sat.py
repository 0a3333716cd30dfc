"""Gated DeltaNet mixers: device time per decode run (a jit_fwd run that starts inside an llm.decode annotation) of the operations under the scopes gdn.proj (W_q, W_k, W_v, the output gate W_g, W_a and W_b), gdn.conv (the one window's read, taps, SiLU, write), gdn.gate (softplus, exp, sigmoid, the L2 norms), gdn.step (the recurrence: the kernel kda_step, filed by its instruction's name), gdn.out_norm and gdn.out_proj, all linear_attention layers; each scope's share in the info line (benchmark/harness/gdn_phases.py)."""
from benchmark.harness import gdn_phases, phases


@phases.quiet
def read(ctx):
    return (gdn_phases.capture(ctx) or {}).get("mixer_ms")
