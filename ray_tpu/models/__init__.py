"""ray_tpu.models — TPU-first reference model families.

Four families run through both the trainer and the serving engine:
GPT-2 (pretrain baseline, BASELINE.json headline metric), Llama
(RoPE/GQA/SwiGLU), OLMoE (the Llama block with QK-norm and dropless
top-k sparse experts, ops/moe.py) and Granite 4.0-H (``granitemoehybrid``:
Mamba-2 state-space layers with an attention layer among every few, a
share of the routed experts plus a shared one, models/granite.py).  All
models are flax.linen with
*logical* dimension names threaded through ray_tpu.parallel.sharding
rules, so DP/FSDP/TP/CP layouts are a rules-table choice, not a model
edit.

``MODEL_FAMILIES`` is the one table the engine (``llm/engine.py``) and
the multi-host training plane (``train.distributed.rules_for_model``)
resolve a family through.  A fifth family is a row here:
its config class, module, init, loss, partition rules, a tiny preset for
tests, and its cache spec (the module's ``__call__`` takes ``kv_cache=``
/ ``positions=`` as GPT2's does, llm/kv_cache.py).  The cache spec
(``CacheSpec``) says what one sequence keeps on the device between steps
and in which layers: how many layers hold K/V in the paged pool and at
what width (``kv_layers`` x ``kv_heads`` x ``head_dim``: every layer for
the first three families), and how many hold a recurrent state and its
shapes (``state_layers``, ``conv_shape``, ``ssm_shape``: none but for
Granite, whose 9 layers in 10 keep a conv window and a float32 state-space
state in a slot and no K/V).  The engine builds both pools from it.
Keys are normalized lowercase-no-separator ("gpt2", "llama", "olmoe",
"granitemoehybrid").
"""

from dataclasses import dataclass
from typing import Any, Callable, Tuple

from .granite import (Granite, GraniteConfig, granite_init,  # noqa: F401
                      granite_loss_fn, granite_partition_rules)

from .gpt2 import (GPT2, GPT2Config, gpt2_init, gpt2_loss_fn,  # noqa: F401
                   gpt2_partition_rules)
from .llama import (Llama, LlamaConfig, llama_init,  # noqa: F401
                    llama_loss_fn, llama_partition_rules, olmoe_loss_fn,
                    olmoe_partition_rules)


@dataclass(frozen=True)
class CacheSpec:
    """What one sequence keeps on the device, by layer kind."""
    kv_layers: int                      # layers with K/V in the paged pool
    kv_heads: int                       # heads the pool stores (grouped)
    head_dim: int
    state_layers: int = 0               # layers with a recurrent state
    conv_shape: Tuple[int, ...] = ()    # one sequence, one layer (dtype)
    ssm_shape: Tuple[int, ...] = ()     # one sequence, one layer, float32


def _attention_only(kv_heads: Callable[[Any], int]):
    return lambda cfg: CacheSpec(cfg.n_layer, kv_heads(cfg),
                                 cfg.d_model // cfg.n_head)


def _granite_cache(cfg: GraniteConfig) -> CacheSpec:
    return CacheSpec(
        cfg.layers_of("attention"), cfg.n_kv_head, cfg.head_dim,
        cfg.layers_of("mamba"), (cfg.mamba_d_conv - 1, cfg.conv_dim),
        (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state))


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
                        _attention_only(lambda cfg: cfg.n_head)),
    "llama": ModelFamily(LlamaConfig, Llama, llama_init, llama_loss_fn,
                         llama_partition_rules, LlamaConfig.tiny,
                         _attention_only(lambda cfg: cfg.n_kv_head)),
    "olmoe": ModelFamily(LlamaConfig, Llama, llama_init, olmoe_loss_fn,
                         olmoe_partition_rules, LlamaConfig.olmoe_tiny,
                         _attention_only(lambda cfg: cfg.n_kv_head)),
    "granitemoehybrid": ModelFamily(
        GraniteConfig, Granite, granite_init, granite_loss_fn,
        granite_partition_rules, GraniteConfig.tiny, _granite_cache),
}


def family_of(model_cfg) -> ModelFamily:
    """The (first) row whose config class ``model_cfg`` is an instance
    of."""
    for fam in MODEL_FAMILIES.values():
        if isinstance(model_cfg, fam.config):
            return fam
    raise TypeError(f"unsupported model_cfg {type(model_cfg)}: no row "
                    "of ray_tpu.models.MODEL_FAMILIES has its class")

