"""The family row of Kimi-Linear (``model_type`` kimi_linear): the
benchmark's configuration keys are the source's (HF config.json), the
program's are ``models/kimi_linear.py``'s.  Imported by name from
benchmark/harness/families.py when a config says ``"family":
"kimi_linear"``.

``num_experts`` counts what is HELD here (a chip's share; ``first_expert``,
0 where absent, says which experts); the router's width is
``published.num_experts`` where the file has a ``published`` group, else
the same number.  The layers are the source's two 1-indexed lists
(``linear_attn_config.kda_layers`` / ``full_attn_layers``).

A tree whose ``ray_tpu`` has no ``models/kimi_linear.py`` (a parent of the
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
        "kimi_linear.py")):
    raise ImportError("this ray_tpu has no models/kimi_linear.py: it "
                      "cannot run a kimi_linear configuration")


def _router_width(c: Dict[str, Any]) -> int:
    return (c.get("published") or {}).get("num_experts", c["num_experts"])


def _layer_types(c: Dict[str, Any]):
    lin, n = c["linear_attn_config"], c["num_hidden_layers"]
    kda, mla = set(lin["kda_layers"]), set(lin["full_attn_layers"])
    if kda & mla or kda | mla != set(range(1, n + 1)):
        raise ValueError("kda_layers and full_attn_layers must part the "
                         f"layers 1..{n} between them")
    return tuple("kda" if i in kda else "mla" for i in range(1, n + 1))


def _sizes(c: Dict[str, Any]) -> Dict[str, int]:
    """``n_layer``, ``d_ff`` and ``n_experts`` are what the readers of the
    ``moe.*`` metrics take (benchmark/harness/moe_phases.py divides the
    engine's ``layer_runs`` by ``n_layer``): the layers that HAVE experts
    (not the leading dense one), one expert's width, the experts HELD.
    ``kv_layers`` are the latent layers, their widths under
    benchmark/families/kimi_k2.py's names (benchmark/harness/mla_phases.py
    reads them); the delta-rule layers under names of their own
    (benchmark/harness/kda_phases.py)."""
    lin = c["linear_attn_config"]
    kinds = _layer_types(c)
    return {"n_layer": max(len(kinds) - c["first_k_dense_replace"], 0),
            "kv_layers": kinds.count("mla"),
            "kda_layers": kinds.count("kda"),
            "kda_heads": lin["num_heads"],
            "kda_head_dim": lin["head_dim"],
            "d_model": c["hidden_size"],
            "n_head": c["num_attention_heads"],
            "d_ff": c["moe_intermediate_size"],
            "n_experts": c["num_experts"],
            "router_experts": _router_width(c),
            "experts_per_token": c["num_experts_per_token"],
            "vocab": c["vocab_size"],
            "max_seq": c["model_max_length"],
            "kv_lora_rank": c["kv_lora_rank"],
            "qk_nope_head_dim": c["qk_nope_head_dim"],
            "qk_rope_head_dim": c["qk_rope_head_dim"],
            "v_head_dim": c["v_head_dim"],
            "head_dim": c["qk_nope_head_dim"] + c["qk_rope_head_dim"]}


def _program_config(c: Dict[str, Any], **overrides):
    import jax.numpy as jnp    # dtype names only: starts no backend

    from ray_tpu.models.kimi import EXPERT_BIAS_STD, ROUTE_NORM_EPS
    from ray_tpu.models.kimi_linear import L2_EPS, KimiLinearConfig

    same = {"route_norm_eps": ROUTE_NORM_EPS,
            "expert_bias_std": EXPERT_BIAS_STD, "l2_norm_eps": L2_EPS,
            "q_lora_rank": None, "mla_use_nope": True,
            "rope_scaling": None, "moe_renormalize": True,
            "moe_router_activation_func": "sigmoid",
            "num_expert_group": 1, "topk_group": 1, "hidden_act": "silu",
            "moe_layer_freq": 1, "tie_word_embeddings": False,
            "num_nextn_predict_layers": 0,
            "num_key_value_heads": c["num_attention_heads"]}
    if any(c[k] != v for k, v in same.items()):
        raise ValueError("models/kimi_linear.py writes the source's "
                         "choices down, not their alternatives")
    lin = c["linear_attn_config"]
    routed, held = _router_width(c), c["num_experts"]
    return KimiLinearConfig(
        vocab_size=c["vocab_size"], layer_types=_layer_types(c),
        d_model=c["hidden_size"], kda_heads=lin["num_heads"],
        kda_head_dim=lin["head_dim"],
        kda_conv=lin["short_conv_kernel_size"],
        kda_gate_rank=c["kda_gate_rank"],
        n_head=c["num_attention_heads"], kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"],
        v_head_dim=c["v_head_dim"], d_ff=c["intermediate_size"],
        n_dense_layers=c["first_k_dense_replace"],
        moe_d_ff=c["moe_intermediate_size"], n_experts=routed,
        experts_per_token=c["num_experts_per_token"],
        n_shared_experts=c["num_shared_experts"],
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        first_expert=c.get("first_expert", 0),
        held_experts=None if held == routed else held,
        max_seq=c["model_max_length"], rms_eps=c["rms_norm_eps"],
        dtype=getattr(jnp, c["compute_dtype"]),
        param_dtype=getattr(jnp, c["param_dtype"]), **overrides)


def _init(cfg, rng):
    from ray_tpu.models.kimi_linear import kimi_linear_init

    return kimi_linear_init(cfg, rng)


def _loss(cfg, params, batch, loss_chunk=0):
    from ray_tpu.models.kimi_linear import kimi_linear_loss_fn

    return kimi_linear_loss_fn(cfg, params, batch)


FAMILIES["kimi_linear"] = Family(
    name="kimi_linear", program_config=_program_config, init=_init,
    loss=_loss, partition_rules="kimi_linear", engine_model="kimilinear",
    reference="kimi_linear_ref", sizes=_sizes)
