"""latent attention: device time per decode run (a jit_fwd run that starts inside an llm.decode annotation) of the operations under the scopes mla.q (W_qa, its norm, W_qb), mla.kv (W_kva, its norm, RoPE on both rotary parts), mla.absorb (q_nope into the latent space through W_kvb's key half, the result out of it through the value half) and attn.out (W_o), all layers: the five projections of the absorbed path, without the attention over the pool (decode.attend_ms.sat) and the rows' store; each scope's share, and moe.shared and mlp.dense beside them, in the info line (benchmark/harness/mla_phases.py)."""
from benchmark.harness import mla_phases, phases


@phases.quiet
def read(ctx):
    return (mla_phases.capture(ctx) or {}).get("proj_ms")
