"""The family row of Kimi-K2 (``model_type`` kimi_k2): the benchmark's
configuration keys are the source's (HF config.json), the program's are
``models/kimi.py``'s.  Imported by name from benchmark/harness/families.py
when a config says ``"family": "kimi_k2"``.

``n_routed_experts`` and ``vocab_size`` count what is HELD here (a chip's
share; ``first_routed_expert``, 0 where absent, says which experts); the
router's width is ``published.n_routed_experts`` where the file has a
``published`` group, else the same number.

A tree whose ``ray_tpu`` has no ``models/kimi.py`` (a parent of the PR that
brought the family) cannot run such a configuration: importing this file
fails there, before a cluster starts."""

from __future__ import annotations

import importlib.util
import os
from typing import Any, Dict

from benchmark.harness.families import FAMILIES, Family

# (by the file, not by importing it: this process imports no model code)
if not os.path.isfile(os.path.join(os.path.dirname(
        importlib.util.find_spec("ray_tpu").origin), "models", "kimi.py")):
    raise ImportError("this ray_tpu has no models/kimi.py: it cannot run "
                      "a kimi_k2 configuration")


def _router_width(c: Dict[str, Any]) -> int:
    return (c.get("published") or {}).get("n_routed_experts",
                                          c["n_routed_experts"])


def _sizes(c: Dict[str, Any]) -> Dict[str, int]:
    """``n_layer``, ``d_ff`` and ``n_experts`` are what the readers of the
    ``moe.*`` metrics take (benchmark/harness/moe_phases.py divides the
    engine's ``layer_runs`` by ``n_layer``): the layers that HAVE experts
    (not the leading dense one), one expert's width, the experts HELD.
    Every layer has latent attention (``kv_layers``); its widths under
    names of their own (benchmark/harness/mla_phases.py reads them)."""
    return {"n_layer": max(c["num_hidden_layers"]
                           - c["first_k_dense_replace"], 0),
            "kv_layers": c["num_hidden_layers"],
            "d_model": c["hidden_size"],
            "n_head": c["num_attention_heads"],
            "d_ff": c["moe_intermediate_size"],
            "n_experts": c["n_routed_experts"],
            "router_experts": _router_width(c),
            "experts_per_token": c["num_experts_per_tok"],
            "vocab": c["vocab_size"],
            "max_seq": c["max_position_embeddings"],
            "q_lora_rank": c["q_lora_rank"],
            "kv_lora_rank": c["kv_lora_rank"],
            "qk_nope_head_dim": c["qk_nope_head_dim"],
            "qk_rope_head_dim": c["qk_rope_head_dim"],
            "v_head_dim": c["v_head_dim"],
            "head_dim": c["qk_nope_head_dim"] + c["qk_rope_head_dim"]}


def _program_config(c: Dict[str, Any], **overrides):
    import jax.numpy as jnp    # dtype names only: starts no backend

    from ray_tpu.models.kimi import (EXPERT_BIAS_STD, ROUTE_NORM_EPS,
                                     KimiK2Config)

    same = {"route_norm_eps": ROUTE_NORM_EPS,
            "expert_bias_std": EXPERT_BIAS_STD, "attention_bias": False,
            "norm_topk_prob": True, "scoring_func": "sigmoid",
            "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
            "hidden_act": "silu", "moe_layer_freq": 1,
            "tie_word_embeddings": False, "num_nextn_predict_layers": 0,
            "num_key_value_heads": c["num_attention_heads"]}
    rs = c["rope_scaling"]
    if any(c[k] != v for k, v in same.items()) or rs["type"] != "yarn":
        raise ValueError("models/kimi.py writes the source's choices down, "
                         "not their alternatives")
    routed, held = _router_width(c), c["n_routed_experts"]
    return KimiK2Config(
        vocab_size=c["vocab_size"], n_layer=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_head=c["num_attention_heads"],
        q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"],
        v_head_dim=c["v_head_dim"], d_ff=c["intermediate_size"],
        n_dense_layers=c["first_k_dense_replace"],
        moe_d_ff=c["moe_intermediate_size"], n_experts=routed,
        experts_per_token=c["num_experts_per_tok"],
        n_shared_experts=c["n_shared_experts"],
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        first_expert=c.get("first_routed_expert", 0),
        held_experts=None if held == routed else held,
        rope_theta=float(c["rope_theta"]), rope_factor=float(rs["factor"]),
        rope_original_max=rs["original_max_position_embeddings"],
        rope_beta_fast=float(rs["beta_fast"]),
        rope_beta_slow=float(rs["beta_slow"]),
        rope_mscale=float(rs["mscale"]),
        rope_mscale_all_dim=float(rs["mscale_all_dim"]),
        max_seq=c["max_position_embeddings"], rms_eps=c["rms_norm_eps"],
        dtype=getattr(jnp, c["compute_dtype"]),
        param_dtype=getattr(jnp, c["param_dtype"]), **overrides)


def _init(cfg, rng):
    from ray_tpu.models.kimi import kimi_k2_init

    return kimi_k2_init(cfg, rng)


def _loss(cfg, params, batch, loss_chunk=0):
    from ray_tpu.models.kimi import kimi_k2_loss_fn

    return kimi_k2_loss_fn(cfg, params, batch)


FAMILIES["kimi_k2"] = Family(
    name="kimi_k2", program_config=_program_config, init=_init,
    loss=_loss, partition_rules="kimi_k2", engine_model="kimik2",
    reference="kimi_k2_ref", sizes=_sizes)
