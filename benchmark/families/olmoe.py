"""The family row of OLMoE (``model_type`` olmoe): the benchmark's
configuration keys are the source's (HF config.json), the program's are
``models/llama.py``'s.  Imported by name from
benchmark/harness/families.py when a config says ``"family": "olmoe"``."""

from __future__ import annotations

from typing import Any, Dict

from benchmark.harness.families import FAMILIES, Family


def _sizes(c: Dict[str, Any]) -> Dict[str, int]:
    return {"n_layer": c["num_hidden_layers"], "d_model": c["hidden_size"],
            "n_head": c["num_attention_heads"],
            "d_ff": c["intermediate_size"],
            "n_experts": c["num_experts"],
            "experts_per_token": c["num_experts_per_tok"],
            "vocab": c["vocab_size"],
            "max_seq": c["max_position_embeddings"],
            "head_dim": c["hidden_size"] // c["num_attention_heads"]}


def _program_config(c: Dict[str, Any], **overrides):
    import jax.numpy as jnp    # dtype names only: starts no backend

    from ray_tpu.models.llama import LlamaConfig

    s = _sizes(c)
    return LlamaConfig(
        vocab_size=s["vocab"], n_layer=s["n_layer"], n_head=s["n_head"],
        n_kv_head=c["num_key_value_heads"], d_model=s["d_model"],
        d_ff=s["d_ff"], max_seq=s["max_seq"],
        rope_theta=float(c["rope_theta"]), rms_eps=c["rms_norm_eps"],
        qk_norm=True, n_experts=s["n_experts"],
        experts_per_token=s["experts_per_token"],
        norm_topk_prob=c["norm_topk_prob"],
        moe_aux_weight=c["router_aux_loss_coef"],
        moe_z_weight=c["router_z_loss_coef"],
        dtype=getattr(jnp, c["compute_dtype"]),
        param_dtype=getattr(jnp, c["param_dtype"]), **overrides)


def _init(cfg, rng):
    from ray_tpu.models.llama import llama_init

    return llama_init(cfg, rng)


def _loss(cfg, params, batch, loss_chunk=0):
    from ray_tpu.models.llama import olmoe_loss_fn

    return olmoe_loss_fn(cfg, params, batch)


FAMILIES["olmoe"] = Family(
    name="olmoe", program_config=_program_config, init=_init, loss=_loss,
    partition_rules="olmoe", engine_model="olmoe", reference="olmoe_ref",
    sizes=_sizes)
