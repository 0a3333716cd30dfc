"""The family row of Olmo-Hybrid (``model_type`` olmo_hybrid): the
benchmark's configuration keys are the source's (HF config.json), the
program's are ``models/olmo_hybrid.py``'s.  Imported by name from
benchmark/harness/families.py when a config says ``"family":
"olmo_hybrid"``.

A tree whose ``ray_tpu`` has no ``models/olmo_hybrid.py`` (a parent of the
PR that brought the family) cannot run such a configuration: importing this
file fails there, before a cluster starts."""

from __future__ import annotations

import importlib.util
import os
from typing import Any, Dict

from benchmark.harness.families import FAMILIES, Family

# (by the file, not by importing it: this process imports no model code)
if not os.path.isfile(os.path.join(os.path.dirname(
        importlib.util.find_spec("ray_tpu").origin), "models",
        "olmo_hybrid.py")):
    raise ImportError("this ray_tpu has no models/olmo_hybrid.py: it "
                      "cannot run an olmo_hybrid configuration")


def _sizes(c: Dict[str, Any]) -> Dict[str, int]:
    """``kv_layers`` are the full-attention layers (attend_phases.py reads
    the engine's counters of their K/V rows), ``gdn_*`` the delta-rule
    layers' own (benchmark/harness/gdn_phases.py); ``n_layer`` every layer
    (each has the dense FFN)."""
    kinds = c["layer_types"]
    return {"n_layer": len(kinds),
            "kv_layers": kinds.count("full_attention"),
            "gdn_layers": kinds.count("linear_attention"),
            "gdn_heads": c["linear_num_value_heads"],
            "gdn_key_dim": c["linear_key_head_dim"],
            "gdn_value_dim": c["linear_value_head_dim"],
            "d_model": c["hidden_size"],
            "n_head": c["num_attention_heads"],
            "d_ff": c["intermediate_size"],
            "vocab": c["vocab_size"],
            "max_seq": c["max_position_embeddings"],
            "head_dim": c["hidden_size"] // c["num_attention_heads"]}


def _program_config(c: Dict[str, Any], **overrides):
    import jax.numpy as jnp    # dtype names only: starts no backend

    from ray_tpu.models.olmo_hybrid import L2_EPS, OlmoHybridConfig

    same = {"model_type": "olmo_hybrid", "hidden_act": "silu",
            "attention_bias": False, "tie_word_embeddings": False,
            "linear_allow_neg_eigval": True,
            "rope_parameters": {"rope_theta": None},
            "linear_num_key_heads": c["linear_num_value_heads"],
            "l2_norm_eps": L2_EPS}
    if any(c[k] != v for k, v in same.items()) \
            or len(c["layer_types"]) != c["num_hidden_layers"]:
        raise ValueError("models/olmo_hybrid.py writes the source's "
                         "choices down, not their alternatives")
    return OlmoHybridConfig(
        vocab_size=c["vocab_size"], layer_types=tuple(c["layer_types"]),
        d_model=c["hidden_size"], n_head=c["num_attention_heads"],
        n_kv_head=c["num_key_value_heads"],
        gdn_heads=c["linear_num_value_heads"],
        gdn_key_dim=c["linear_key_head_dim"],
        gdn_value_dim=c["linear_value_head_dim"],
        gdn_conv=c["linear_conv_kernel_dim"],
        d_ff=c["intermediate_size"], max_seq=c["max_position_embeddings"],
        rms_eps=c["rms_norm_eps"],
        dtype=getattr(jnp, c["compute_dtype"]),
        param_dtype=getattr(jnp, c["param_dtype"]), **overrides)


def _init(cfg, rng):
    from ray_tpu.models.olmo_hybrid import olmo_hybrid_init

    return olmo_hybrid_init(cfg, rng)


def _loss(cfg, params, batch, loss_chunk=0):
    from ray_tpu.models.olmo_hybrid import olmo_hybrid_loss_fn

    return olmo_hybrid_loss_fn(cfg, params, batch)


FAMILIES["olmo_hybrid"] = Family(
    name="olmo_hybrid", program_config=_program_config, init=_init,
    loss=_loss, partition_rules="olmo_hybrid", engine_model="olmohybrid",
    reference="olmo_hybrid_ref", sizes=_sizes)
