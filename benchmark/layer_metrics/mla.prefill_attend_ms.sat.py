"""latent attention: device time per prefill run (a jit_fwd run that starts inside an llm.prefill annotation) of the operations under mla.expand (c_kv through W_kvb to every head's keys and values) and the rest of attn.core (the causal attention among the prompt's rows: the flash kernel with the transposes and the padding around it), all layers, the mean over the capture's prefill runs; by bucket in the info line (benchmark/harness/mla_phases.py). The rows' store is not in it."""
from benchmark.harness import mla_phases, phases


@phases.quiet
def read(ctx):
    return (mla_phases.capture(ctx) or {}).get("prefill_attend_ms")
