"""The family row of Cohere2-MoE (``model_type`` cohere2_moe; Command A+):
the benchmark's configuration keys are the source's (HF config.json), the
program's are ``models/cohere.py``'s.  Imported by name from
benchmark/harness/families.py when a config says ``"family":
"cohere2_moe"``.

A tree whose ``ray_tpu`` has no ``models/cohere.py`` (a parent of the PR
that brought the family) cannot run such a configuration: importing this
file fails there, before a cluster starts."""

from __future__ import annotations

import importlib.util
import os
from typing import Any, Dict

from benchmark.harness.families import FAMILIES, Family

# (by the file, not by importing it: this process imports no model code)
if not os.path.isfile(os.path.join(os.path.dirname(
        importlib.util.find_spec("ray_tpu").origin), "models",
        "cohere.py")):
    raise ImportError("this ray_tpu has no models/cohere.py: it cannot "
                      "run a cohere2_moe configuration")


def _sizes(c: Dict[str, Any]) -> Dict[str, int]:
    """``kv_layers`` are the layers that keep every position,
    ``window_layers`` those that keep ``window`` (benchmark/harness/
    swa_phases.py reads the engine's counters of both groups); ``n_layer``
    every layer (each has the experts and the shared experts);
    ``n_experts`` the router's width, ``held_experts`` this chip's share."""
    kinds = c["layer_types"]
    return {"n_layer": len(kinds),
            "kv_layers": kinds.count("full_attention"),
            "window_layers": kinds.count("sliding_attention"),
            "window": c["sliding_window"],
            "d_model": c["hidden_size"],
            "n_head": c["num_attention_heads"],
            "n_kv_head": c["num_key_value_heads"],
            "head_dim": c["head_dim"],
            "d_ff": c["intermediate_size"],
            "n_experts": c.get("published", c)["num_experts"],
            "held_experts": c["num_experts"],
            "top_k": c["num_experts_per_tok"],
            "n_shared_experts": c["num_shared_experts"],
            "vocab": c["vocab_size"],
            "max_seq": c["max_position_embeddings"]}


def _program_config(c: Dict[str, Any], **overrides):
    import jax.numpy as jnp    # dtype names only: starts no backend

    from ray_tpu.models.cohere import Cohere2MoeConfig

    same = {"model_type": "cohere2_moe", "hidden_act": "silu",
            "attention_bias": False, "tie_word_embeddings": True,
            "use_parallel_block": True, "use_qk_norm": False,
            "expert_selection_fn": "sigmoid", "norm_topk_prob": True,
            "rotary_pct": 1, "position_embedding_type": "rope_gptj",
            "shared_expert_combination_strategy": "average",
            "first_k_dense_replace": 0, "use_gated_activation": True}
    if any(c[k] != v for k, v in same.items()) \
            or len(c["layer_types"]) != c["num_hidden_layers"]:
        raise ValueError("models/cohere.py writes the source's choices "
                         "down, not their alternatives")
    router = c.get("published", c)["num_experts"]
    return Cohere2MoeConfig(
        vocab_size=c["vocab_size"], layer_types=tuple(c["layer_types"]),
        d_model=c["hidden_size"], n_head=c["num_attention_heads"],
        n_kv_head=c["num_key_value_heads"], head_dim=c["head_dim"],
        sliding_window=c["sliding_window"], d_ff=c["intermediate_size"],
        n_experts=router, experts_per_token=c["num_experts_per_tok"],
        n_shared_experts=c["num_shared_experts"],
        first_expert=c.get("first_expert", 0),
        held_experts=None if c["num_experts"] == router
        else c["num_experts"],
        logit_scale=float(c["logit_scale"]),
        max_seq=c["max_position_embeddings"],
        rope_theta=float(c["rope_theta"]), rms_eps=c["layer_norm_eps"],
        dtype=getattr(jnp, c["compute_dtype"]),
        param_dtype=getattr(jnp, c["param_dtype"]), **overrides)


def _init(cfg, rng):
    from ray_tpu.models.cohere import cohere2_moe_init

    return cohere2_moe_init(cfg, rng)


def _loss(cfg, params, batch, loss_chunk=0):
    from ray_tpu.models.cohere import cohere2_moe_loss_fn

    return cohere2_moe_loss_fn(cfg, params, batch)


FAMILIES["cohere2_moe"] = Family(
    name="cohere2_moe", program_config=_program_config, init=_init,
    loss=_loss, partition_rules="cohere2_moe", engine_model="cohere2moe",
    reference="cohere2_moe_ref", sizes=_sizes)
