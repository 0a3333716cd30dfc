"""The family row of Xing4.0 (``model_type`` xing4_0): the benchmark's
configuration keys are the source's (HF config.json), the program's are
``models/xing.py``'s.  Imported by name from benchmark/harness/families.py
when a config says ``"family": "xing4_0"``.

Every expert and the whole vocabulary are HELD here (the source's own
``ep_size`` is 1); ``n_routed_experts`` counts them as in Kimi-K2's row, and
a ``published`` group may give the router a wider one.

A tree whose ``ray_tpu`` has no ``models/xing.py`` (a parent of the PR that
brought the family) cannot run such a configuration: importing this file
fails there, before a cluster starts."""

from __future__ import annotations

import importlib.util
import os
from typing import Any, Dict

from benchmark.harness.families import FAMILIES, Family

# (by the file, not by importing it: this process imports no model code)
if not os.path.isfile(os.path.join(os.path.dirname(
        importlib.util.find_spec("ray_tpu").origin), "models", "xing.py")):
    raise ImportError("this ray_tpu has no models/xing.py: it cannot run "
                      "a xing4_0 configuration")

from benchmark.families import kimi_k2  # noqa: E402 — its sizes' names

# What models/xing.py writes down, and not its alternatives.
SAME = {"attention_bias": False, "norm_topk_prob": True,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
        "topk_group": 1, "hidden_act": "silu", "moe_layer_freq": 1,
        "tie_word_embeddings": False, "num_nextn_predict_layers": 0}


def _sizes(c: Dict[str, Any]) -> Dict[str, int]:
    """Kimi-K2's names (benchmark/harness/mla_phases.py and moe_phases.py
    read them: ``n_layer`` the layers that HAVE experts, ``kv_layers`` all),
    and the residual path's: the streams and the sublayers
    (benchmark/harness/hc_phases.py)."""
    return {**kimi_k2._sizes(c), "hc_mult": c["hc_mult"],
            "hc_sublayers": 2 * c["num_hidden_layers"]}


def _program_config(c: Dict[str, Any], **overrides):
    import dataclasses

    from ray_tpu.models.xing import (HC_BIAS_STD, HC_GATES, HC_RES_DIAG,
                                     XingConfig)

    same = {**SAME, "hc_init": {
        "map_gate": list(HC_GATES), "map_bias_std": list(HC_BIAS_STD),
        "b_res_diagonal": HC_RES_DIAG, "phi_std": 0.02}}
    wrong = {k: c[k] for k, v in same.items() if c[k] != v}
    if wrong:
        raise ValueError("models/xing.py writes the source's choices down, "
                         f"not their alternatives: {wrong}")
    # Kimi-K2's fields (and its row's own refusals), then the hc keys
    base = kimi_k2._program_config(c, **overrides)
    return XingConfig(
        **{f.name: getattr(base, f.name) for f in dataclasses.fields(base)},
        hc_mult=c["hc_mult"], hc_sinkhorn_iters=c["hc_sinkhorn_iters"],
        hc_eps=float(c["hc_eps"]),
        hc_clamp_min=float(c["mhc_h_res_clamp_min"]),
        hc_clamp_max=float(c["mhc_h_res_clamp_max"]))


def _init(cfg, rng):
    from ray_tpu.models.xing import xing_init

    return xing_init(cfg, rng)


def _loss(cfg, params, batch, loss_chunk=0):
    from ray_tpu.models.xing import xing_loss_fn

    return xing_loss_fn(cfg, params, batch)


FAMILIES["xing4_0"] = Family(
    name="xing4_0", program_config=_program_config, init=_init,
    loss=_loss, partition_rules="xing40", engine_model="xing40",
    reference="xing4_0_ref", sizes=_sizes)
