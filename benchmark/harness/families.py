"""Model family -> the program's config class and init function, and the
benchmark's plain reference.  ONE table that takes new rows: a new family
adds a row here (a new file may register itself with ``FAMILIES[...] =``
from benchmark/families/<family>.py, imported by name below)."""

from __future__ import annotations

import importlib
from dataclasses import dataclass
from typing import Any, Callable, Dict


@dataclass(frozen=True)
class Family:
    name: str
    program_config: Callable[..., Any]   # (config json, **overrides)
    init: Callable[[Any, Any], Any]      # (program config, rng) -> params
    loss: Callable[..., Any]             # (cfg, params, batch, loss_chunk=)
    partition_rules: str                 # train.rules_for_model(name)
    engine_model: str                    # llm_deployment(model=...)
    reference: str                       # module under benchmark.reference
    sizes: Callable[[Dict[str, Any]], Dict[str, int]]


def _gpt2_sizes(c: Dict[str, Any]) -> Dict[str, int]:
    return {"n_layer": c["n_layer"], "d_model": c["n_embd"],
            "n_head": c["n_head"], "d_ff": c["n_inner"],
            "vocab": c["vocab_size"], "max_seq": c["n_positions"],
            "head_dim": c["n_embd"] // c["n_head"]}


def _gpt2_program_config(c: Dict[str, Any], **overrides):
    import jax.numpy as jnp    # dtype names only: starts no backend

    from ray_tpu.models.gpt2 import GPT2Config

    s = _gpt2_sizes(c)
    return GPT2Config(vocab_size=s["vocab"], n_layer=s["n_layer"],
                      n_head=s["n_head"], d_model=s["d_model"],
                      d_ff=s["d_ff"], max_seq=s["max_seq"],
                      dtype=getattr(jnp, c["compute_dtype"]), **overrides)


def _gpt2_init(cfg, rng):
    from ray_tpu.models.gpt2 import gpt2_init

    return gpt2_init(cfg, rng)


def _gpt2_loss(cfg, params, batch, loss_chunk=0):
    from ray_tpu.models.gpt2 import gpt2_loss_fn

    return gpt2_loss_fn(cfg, params, batch, loss_chunk=loss_chunk)


FAMILIES: Dict[str, Family] = {
    "gpt2": Family(name="gpt2", program_config=_gpt2_program_config,
                   init=_gpt2_init, loss=_gpt2_loss,
                   partition_rules="gpt2", engine_model="gpt2",
                   reference="gpt2_ref", sizes=_gpt2_sizes),
}


def family_of(config: Dict[str, Any]) -> Family:
    name = config.get("family")
    if name not in FAMILIES:
        # A family that came after this file: benchmark/families/<name>.py
        # registers its row when imported.
        try:
            importlib.import_module(f"benchmark.families.{name}")
        except ImportError:
            pass
    if name not in FAMILIES:
        raise LookupError(
            f"model family {name!r} has no row in "
            "benchmark/harness/families.py and no file "
            f"benchmark/families/{name}.py that registers one")
    return FAMILIES[name]
