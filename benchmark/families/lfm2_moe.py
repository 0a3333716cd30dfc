"""The family row of LFM2-MoE (``model_type`` lfm2_moe): the benchmark's
configuration keys are the source's (HF config.json), the program's are
``models/lfm2.py``'s.  Imported by name from benchmark/harness/families.py
when a config says ``"family": "lfm2_moe"``.

A tree whose ``ray_tpu`` has no ``models/lfm2.py`` (a parent of the PR
that brought the family) cannot run such a configuration: importing this
file fails there, before a cluster starts."""

from __future__ import annotations

import importlib.util
import os
from typing import Any, Dict

from benchmark.harness.families import FAMILIES, Family

# (by the file, not by importing it: this process imports no model code)
if not os.path.isfile(os.path.join(os.path.dirname(
        importlib.util.find_spec("ray_tpu").origin), "models", "lfm2.py")):
    raise ImportError("this ray_tpu has no models/lfm2.py: it cannot run "
                      "an lfm2_moe configuration")


def _layer_types(c: Dict[str, Any]):
    return tuple(c["layer_types"][:c["num_hidden_layers"]])


def _sizes(c: Dict[str, Any]) -> Dict[str, int]:
    """``n_layer``, ``d_ff`` and ``n_experts`` are what the readers of the
    ``moe.*`` metrics take (benchmark/harness/moe_phases.py divides the
    engine's ``layer_runs`` by ``n_layer``): the layers that HAVE experts
    (not the leading dense ones), one expert's width, the experts held
    (all).  The short-conv and the K/V layers under names of their own
    (benchmark/harness/conv_phases.py reads ``conv_layers``)."""
    kinds = _layer_types(c)
    return {"n_layer": max(len(kinds) - c["num_dense_layers"], 0),
            "conv_layers": kinds.count("conv"),
            "kv_layers": kinds.count("full_attention"),
            "d_model": c["hidden_size"],
            "n_head": c["num_attention_heads"],
            "d_ff": c["moe_intermediate_size"],
            "n_experts": c["num_experts"],
            "experts_per_token": c["num_experts_per_tok"],
            "vocab": c["vocab_size"],
            "max_seq": c["max_position_embeddings"],
            "head_dim": c["hidden_size"] // c["num_attention_heads"]}


def _program_config(c: Dict[str, Any], **overrides):
    import jax.numpy as jnp    # dtype names only: starts no backend

    from ray_tpu.models.lfm2 import (EXPERT_BIAS_STD, ROUTE_NORM_EPS,
                                     Lfm2Config)

    same = {"route_norm_eps": ROUTE_NORM_EPS,
            "expert_bias_std": EXPERT_BIAS_STD, "conv_bias": False,
            "use_expert_bias": True, "norm_topk_prob": True,
            "routed_scaling_factor": 1, "tie_word_embeddings": True}
    if any(c[k] != v for k, v in same.items()) \
            or c["rope_parameters"]["rope_type"] != "default":
        raise ValueError("models/lfm2.py writes the source's choices down, "
                         "not their alternatives")
    return Lfm2Config(
        vocab_size=c["vocab_size"], layer_types=_layer_types(c),
        d_model=c["hidden_size"], n_head=c["num_attention_heads"],
        n_kv_head=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        n_dense_layers=c["num_dense_layers"],
        moe_d_ff=c["moe_intermediate_size"], n_experts=c["num_experts"],
        experts_per_token=c["num_experts_per_tok"],
        conv_taps=c["conv_L_cache"],
        rope_theta=float(c["rope_parameters"]["rope_theta"]),
        max_seq=c["max_position_embeddings"], rms_eps=c["norm_eps"],
        dtype=getattr(jnp, c["compute_dtype"]),
        param_dtype=getattr(jnp, c["param_dtype"]), **overrides)


def _init(cfg, rng):
    from ray_tpu.models.lfm2 import lfm2_init

    return lfm2_init(cfg, rng)


def _loss(cfg, params, batch, loss_chunk=0):
    from ray_tpu.models.lfm2 import lfm2_loss_fn

    return lfm2_loss_fn(cfg, params, batch)


FAMILIES["lfm2_moe"] = Family(
    name="lfm2_moe", program_config=_program_config, init=_init,
    loss=_loss, partition_rules="lfm2_moe", engine_model="lfm2moe",
    reference="lfm2_moe_ref", sizes=_sizes)
