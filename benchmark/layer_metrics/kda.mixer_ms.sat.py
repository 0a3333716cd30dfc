"""delta-rule mixers: device time per decode run (a jit_fwd run that starts inside an llm.decode annotation) of the operations under the scopes kda.proj (W_q, W_k, W_v, W_b and both low-rank gates), kda.conv (the three windows' read, taps, SiLU, write), kda.gate (softplus, exp, sigmoid, the L2 norms), kda.step (the recurrence with its state read and write), kda.out_norm and kda.out_proj, all KDA layers; each scope's share in the info line, and beside it the bytes the mixers must move (benchmark/harness/kda_flops.py) with their least time at the chip's bandwidth: held against no roofline (conv_phases.py has the readings that say why)."""
from benchmark.harness import kda_phases, phases


@phases.quiet
def read(ctx):
    kda_phases.mixer_floor(ctx)
    return (kda_phases.capture(ctx) or {}).get("mixer_ms")
