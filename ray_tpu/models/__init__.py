"""ray_tpu.models — TPU-first reference model families.

Three families run through both the trainer and the serving engine:
GPT-2 (pretrain baseline, BASELINE.json headline metric), Llama
(RoPE/GQA/SwiGLU) and OLMoE (the Llama block with QK-norm and dropless
top-k sparse experts, ops/moe.py).  All models are flax.linen with
*logical* dimension names threaded through ray_tpu.parallel.sharding
rules, so DP/FSDP/TP/CP layouts are a rules-table choice, not a model
edit.

``MODEL_FAMILIES`` is the one table the engine (``llm/engine.py``) and
the multi-host training plane (``train.distributed.rules_for_model``)
resolve a family through.  A fourth family is a row here:
its config class, module, init, loss, partition rules, a tiny preset for
tests, and how many KV heads its cache stores (the module's ``__call__``
takes ``kv_cache=`` / ``positions=`` as GPT2's does, llm/kv_cache.py).
Keys are normalized lowercase-no-separator ("gpt2", "llama", "olmoe").
"""

from dataclasses import dataclass
from typing import Any, Callable

from .gpt2 import (GPT2, GPT2Config, gpt2_init, gpt2_loss_fn,  # noqa: F401
                   gpt2_partition_rules)
from .llama import (Llama, LlamaConfig, llama_init,  # noqa: F401
                    llama_loss_fn, llama_partition_rules, olmoe_loss_fn,
                    olmoe_partition_rules)


@dataclass(frozen=True)
class ModelFamily:
    config: type                       # its config dataclass
    module: type                       # flax module: module(cfg)
    init: Callable[[Any, Any], Any]    # (cfg, rng) -> params
    loss: Callable[..., Any]           # (cfg, params, batch) -> scalar
    partition_rules: Callable[[], Any]
    tiny: Callable[[], Any]            # a preset for tests
    kv_heads: Callable[[Any], int]     # heads the paged cache stores


MODEL_FAMILIES = {
    "gpt2": ModelFamily(GPT2Config, GPT2, gpt2_init, gpt2_loss_fn,
                        gpt2_partition_rules, GPT2Config.tiny,
                        lambda cfg: cfg.n_head),
    "llama": ModelFamily(LlamaConfig, Llama, llama_init, llama_loss_fn,
                         llama_partition_rules, LlamaConfig.tiny,
                         lambda cfg: cfg.n_kv_head),
    "olmoe": ModelFamily(LlamaConfig, Llama, llama_init, olmoe_loss_fn,
                         olmoe_partition_rules, LlamaConfig.olmoe_tiny,
                         lambda cfg: cfg.n_kv_head),
}


def family_of(model_cfg) -> ModelFamily:
    """The (first) row whose config class ``model_cfg`` is an instance
    of."""
    for fam in MODEL_FAMILIES.values():
        if isinstance(model_cfg, fam.config):
            return fam
    raise TypeError(f"unsupported model_cfg {type(model_cfg)}: no row "
                    "of ray_tpu.models.MODEL_FAMILIES has its class")

