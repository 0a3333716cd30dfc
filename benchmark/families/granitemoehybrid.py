"""The family row of Granite 4.0-H (``model_type`` granitemoehybrid): the
benchmark's configuration keys are the source's (HF config.json), the
program's are ``models/granite.py``'s.  Imported by name from
benchmark/harness/families.py when a config says ``"family":
"granitemoehybrid"``.

``num_local_experts`` in a configuration file is the number of routed
experts HELD (the chip's share; ``first_local_expert`` the first of them,
0 where absent); the router's width is ``published.num_local_experts``
where the file has a ``published`` group, else the same number."""

from __future__ import annotations

from typing import Any, Dict

from benchmark.harness.families import FAMILIES, Family


def _sizes(c: Dict[str, Any]) -> Dict[str, int]:
    """``n_layer``, ``d_ff`` and ``n_experts`` are what the readers of the
    ``moe.*`` metrics take: every layer has experts, an expert is ``d_ff``
    wide, ``n_experts`` are held here."""
    return {"n_layer": c["num_hidden_layers"], "d_model": c["hidden_size"],
            "n_head": c["num_attention_heads"],
            "d_ff": c["intermediate_size"],
            "n_experts": c["num_local_experts"],
            "experts_per_token": c["num_experts_per_tok"],
            "vocab": c["vocab_size"],
            "max_seq": c["max_position_embeddings"],
            "head_dim": c["hidden_size"] // c["num_attention_heads"],
            "ssm_layers": c["layer_types"][:c["num_hidden_layers"]]
            .count("mamba")}


def _program_config(c: Dict[str, Any], **overrides):
    import jax.numpy as jnp    # dtype names only: starts no backend

    from ray_tpu.models.granite import GraniteConfig

    if c["position_embedding_type"] != "nope" or c["mamba_proj_bias"] \
            or c["attention_bias"] or not c["mamba_conv_bias"] \
            or not c["tie_word_embeddings"] or c["mamba_expand"] \
            * c["hidden_size"] != c["mamba_n_heads"] * c["mamba_d_head"]:
        raise ValueError("models/granite.py writes the source's choices "
                         "down, not their alternatives")
    routed = (c.get("published") or {}).get("num_local_experts",
                                            c["num_local_experts"])
    held = c["num_local_experts"]
    return GraniteConfig(
        vocab_size=c["vocab_size"],
        layer_types=tuple(c["layer_types"][:c["num_hidden_layers"]]),
        d_model=c["hidden_size"], n_head=c["num_attention_heads"],
        n_kv_head=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        shared_d_ff=c["shared_intermediate_size"], n_experts=routed,
        experts_per_token=c["num_experts_per_tok"],
        first_expert=c.get("first_local_expert", 0),
        held_experts=None if held == routed else held,
        mamba_n_heads=c["mamba_n_heads"], mamba_d_head=c["mamba_d_head"],
        mamba_d_state=c["mamba_d_state"], mamba_n_groups=c["mamba_n_groups"],
        mamba_d_conv=c["mamba_d_conv"], mamba_chunk=c["mamba_chunk_size"],
        embedding_multiplier=float(c["embedding_multiplier"]),
        residual_multiplier=float(c["residual_multiplier"]),
        attention_multiplier=float(c["attention_multiplier"]),
        logits_scaling=float(c["logits_scaling"]),
        max_seq=c["max_position_embeddings"], rms_eps=c["rms_norm_eps"],
        dtype=getattr(jnp, c["compute_dtype"]),
        param_dtype=getattr(jnp, c["param_dtype"]), **overrides)


def _init(cfg, rng):
    from ray_tpu.models.granite import granite_init

    return granite_init(cfg, rng)


def _loss(cfg, params, batch, loss_chunk=0):
    from ray_tpu.models.granite import granite_loss_fn

    return granite_loss_fn(cfg, params, batch)


FAMILIES["granitemoehybrid"] = Family(
    name="granitemoehybrid", program_config=_program_config, init=_init,
    loss=_loss, partition_rules="granitemoehybrid",
    engine_model="granitemoehybrid", reference="granitemoehybrid_ref",
    sizes=_sizes)
