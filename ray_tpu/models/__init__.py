"""ray_tpu.models — TPU-first reference model families.

Ten families run through both the trainer and the serving engine:
GPT-2 (pretrain baseline, BASELINE.json headline metric), Llama
(RoPE/GQA/SwiGLU), OLMoE (the Llama block with QK-norm and dropless
top-k sparse experts, ops/moe.py), Granite 4.0-H (``granitemoehybrid``:
Mamba-2 state-space layers with an attention layer among every few, a
share of the routed experts plus a shared one, models/granite.py) and
LFM2-MoE (``lfm2moe``: gated short-convolution mixers whose whole
recurrent state is a two-row window, grouped-query attention with a
per-head QK-norm among every few, two dense layers ahead of experts
routed by sigmoid scores and a selection bias, models/lfm2.py) and
Kimi-K2 (``kimik2``: multi-head LATENT attention whose cache row is one
compressed vector and a rotary part shared by all heads, absorbed for a
decode step and expanded for a prefill, YaRN's rotary frequencies, a
dense layer ahead of a shared expert beside routed ones,
models/kimi.py) and Kimi-Linear (``kimilinear``: Kimi Delta Attention, a
gated delta rule with a decay per key channel whose state is a matrix a
head, three layers in four, and Kimi-K2's latent attention with no
position encoding in the fourth, over Kimi-K2's FFN,
models/kimi_linear.py) and Xing4.0 (``xing40``: Kimi-K2's layer with the
residual path changed: FOUR streams a token, which every sublayer reads,
writes and mixes through maps it computes from them, a sigmoid read map, a
write map and a Sinkhorn-normalised 4 x 4 stream map: manifold-constrained
hyper-connections, models/xing.py) and Olmo-Hybrid (``olmohybrid``: Gated
DeltaNet mixers, the delta rule with ONE decay a head, betas up to 2 and a
rectangular 96 x 192 state a head, three layers in four, and full
multi-head attention with a QK-norm and no position encoding in the fourth;
every FFN dense; each norm on its sublayer's OUTPUT,
models/olmo_hybrid.py) and Command A+ (``cohere2moe``: three
sliding-window layers with RoPE over adjacent pairs, which keep a ring of
4,096 positions a sequence, beside one full-attention layer without a
position encoding, which keeps them all; a PARALLEL block, attention and
experts under ONE LayerNorm; four shared experts averaged; a tied head,
models/cohere.py).  All but GPT-2 are ONE decoder
(models/decoder.py: the layer loop, the block, grouped-query attention
around the core of models/attention.py, the FFN, the loss, the rules every
tree shares) over a config; what more than one mixer is built from
(RMSNorm, RoPE with or without YaRN, the conv over a slot's window, the
init by leaf) is in models/layers.py.  All models are flax.linen with
*logical* dimension names threaded through ray_tpu.parallel.sharding
rules, so DP/FSDP/TP/CP layouts are a rules-table choice, not a model
edit.

``MODEL_FAMILIES`` is the one table the engine (``llm/engine.py``) and
the multi-host training plane (``train.distributed.rules_for_model``)
resolve a family through.  A ROW is a config class, its module, init,
loss, partition rules, a tiny preset for tests, and its cache spec.  An
eleventh family is a config (published sizes, ``tiny``; ``layer_types``,
one entry a layer; ``mixers``, which maps each entry to its KIND; what
the FFN reads: ``n_dense_layers``, ``experts``, ``shared_d_ff``; and,
where the layers hand one another more than one stream, ``residual``, the
RESIDUAL kind, ``decoder.Residual``: how the state begins and ends and how
a sublayer reads and writes it; ``norm_output`` where each norm lies on its
sublayer's output; ``parallel_block`` where both sublayers read ONE norm;
``norm`` where that is not ``RMSNorm``), the kind it adds, and a row whose
module
is ``Decoder`` under its name (a config may extend another row's:
``family_of`` takes the row of the config's own class first).  A
KIND (``decoder.Mixer``) says three things in one place: the module that
computes the mixer (``(y, cache) -> out`` or ``(out, what it updated)``),
the names it has in the tree, and what it keeps on the device between
steps (which of ``k_pages`` / ``v_pages`` / ``latent_pages`` / ``conv`` /
``ssm``, indexed through the page table or the row's slot, and the
shapes).  The decoder's hand-over of the cache to each layer and the row's
``CacheSpec`` (``decoder.cache_spec``) are both read from the kinds.

The cache spec says what one sequence keeps on the device between steps
and in which layers: how many layers hold K/V in the paged pool and at
what width (``kv_layers`` x ``kv_heads`` x ``head_dim``: every layer for
the first three families), and how many hold a recurrent state and its
shapes (``state_layers``, ``conv_shape``, ``ssm_shape``: none for the
first three; Granite's 9 layers in 10 keep a conv window and a float32
state-space state in a slot and no K/V; LFM2's 3 in 4 keep a conv window
alone, ``ssm_shape == ()``).  A family with latent attention keeps NO
K/V: its ``kv_layers`` hold ONE row a position in a single pool,
``latent_dim`` + ``rope_dim`` numbers padded to whole 128-lane tiles
(``row_width``; Kimi-K2: 512 + 64 -> 640), and ``kv_heads`` /
``head_dim`` are 0.  The two kinds of pool are independent: Kimi-Linear's
spec has latent rows for its 7 latent layers (``latent_dim`` > 0) AND a
slot for its 20 recurrent ones (``state_layers`` > 0: the three
convolutions' window and a float32 ``[heads, d_k, d_v]`` state), and a
layer indexes its pool by its number among its own kind; Olmo-Hybrid's
has K/V for its attention layers AND a slot for its delta-rule ones, whose
``ssm_shape`` is the step kernel's layout of the heads' rectangular states
(``ops/delta_rule.py state_shape``).  A family with sliding-window
layers keeps its K/V in TWO GROUPS: ``kv_layers`` that hold every
position through ``page_table``, and ``window_layers`` that hold a ring
of ``window`` positions a sequence through ``window_table``
(``window_k_pages`` / ``window_v_pages``; Command A+: 1 and 3 of every 4
layers, a window of 4,096).  The engine
builds both pools from the spec
(``llm/kv_cache.py init_pool`` / ``init_state``), and of each the
arrays the spec has and nothing else.
Keys are normalized lowercase-no-separator ("gpt2", "llama", "olmoe",
"granitemoehybrid", "lfm2moe", "kimik2", "kimilinear", "xing40",
"olmohybrid", "cohere2moe").
"""

from dataclasses import dataclass
from typing import Any, Callable

from .cohere import (Cohere2Moe, Cohere2MoeConfig,  # noqa: F401
                     cohere2_moe_init, cohere2_moe_loss_fn,
                     cohere2_moe_partition_rules)
from .decoder import CacheSpec, cache_spec
from .gpt2 import (GPT2, GPT2Config, gpt2_init, gpt2_loss_fn,  # noqa: F401
                   gpt2_partition_rules)
from .granite import (Granite, GraniteConfig, granite_init,  # noqa: F401
                      granite_loss_fn, granite_partition_rules)
from .kimi import (KimiK2, KimiK2Config, kimi_k2_init,  # noqa: F401
                   kimi_k2_loss_fn, kimi_k2_partition_rules)
from .kimi_linear import (KimiLinear, KimiLinearConfig,  # noqa: F401
                          kimi_linear_init, kimi_linear_loss_fn,
                          kimi_linear_partition_rules)
from .lfm2 import (Lfm2, Lfm2Config, lfm2_init,  # noqa: F401
                   lfm2_loss_fn, lfm2_partition_rules)
from .llama import (Llama, LlamaConfig, llama_init,  # noqa: F401
                    llama_loss_fn, llama_partition_rules, olmoe_loss_fn,
                    olmoe_partition_rules)
from .olmo_hybrid import (OlmoHybrid, OlmoHybridConfig,  # noqa: F401
                          olmo_hybrid_init, olmo_hybrid_loss_fn,
                          olmo_hybrid_partition_rules)
from .xing import (Xing, XingConfig, xing_init,  # noqa: F401
                   xing_loss_fn, xing_partition_rules)


@dataclass(frozen=True)
class ModelFamily:
    config: type                       # its config dataclass
    module: type                       # flax module: module(cfg)
    init: Callable[[Any, Any], Any]    # (cfg, rng) -> params
    loss: Callable[..., Any]           # (cfg, params, batch) -> scalar
    partition_rules: Callable[[], Any]
    tiny: Callable[[], Any]            # a preset for tests
    cache: Callable[[Any], CacheSpec]  # what a sequence keeps, by layer


MODEL_FAMILIES = {
    "gpt2": ModelFamily(GPT2Config, GPT2, gpt2_init, gpt2_loss_fn,
                        gpt2_partition_rules, GPT2Config.tiny,
                        lambda cfg: CacheSpec(cfg.n_layer, cfg.n_head,
                                              cfg.d_model // cfg.n_head)),
    "llama": ModelFamily(LlamaConfig, Llama, llama_init, llama_loss_fn,
                         llama_partition_rules, LlamaConfig.tiny,
                         cache_spec),
    "olmoe": ModelFamily(LlamaConfig, Llama, llama_init, olmoe_loss_fn,
                         olmoe_partition_rules, LlamaConfig.olmoe_tiny,
                         cache_spec),
    "granitemoehybrid": ModelFamily(
        GraniteConfig, Granite, granite_init, granite_loss_fn,
        granite_partition_rules, GraniteConfig.tiny, cache_spec),
    "lfm2moe": ModelFamily(Lfm2Config, Lfm2, lfm2_init, lfm2_loss_fn,
                           lfm2_partition_rules, Lfm2Config.tiny,
                           cache_spec),
    "kimik2": ModelFamily(KimiK2Config, KimiK2, kimi_k2_init,
                          kimi_k2_loss_fn, kimi_k2_partition_rules,
                          KimiK2Config.tiny, cache_spec),
    "kimilinear": ModelFamily(
        KimiLinearConfig, KimiLinear, kimi_linear_init,
        kimi_linear_loss_fn, kimi_linear_partition_rules,
        KimiLinearConfig.tiny, cache_spec),
    "xing40": ModelFamily(XingConfig, Xing, xing_init, xing_loss_fn,
                          xing_partition_rules, XingConfig.tiny,
                          cache_spec),
    "olmohybrid": ModelFamily(
        OlmoHybridConfig, OlmoHybrid, olmo_hybrid_init,
        olmo_hybrid_loss_fn, olmo_hybrid_partition_rules,
        OlmoHybridConfig.tiny, cache_spec),
    "cohere2moe": ModelFamily(
        Cohere2MoeConfig, Cohere2Moe, cohere2_moe_init,
        cohere2_moe_loss_fn, cohere2_moe_partition_rules,
        Cohere2MoeConfig.tiny, cache_spec),
}


def family_of(model_cfg) -> ModelFamily:
    """The (first) row whose config class is ``model_cfg``'s own, else
    the first it is an instance of (a config may extend another row's:
    ``XingConfig`` is Kimi-K2's fields and more)."""
    rows = list(MODEL_FAMILIES.values())
    for fam in [f for f in rows if type(model_cfg) is f.config] + rows:
        if isinstance(model_cfg, fam.config):
            return fam
    raise TypeError(f"unsupported model_cfg {type(model_cfg)}: no row "
                    "of ray_tpu.models.MODEL_FAMILIES has its class")

