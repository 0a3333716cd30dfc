"""Compile each cell's programs at their real sizes for a DESCRIBED v5e:2x2
(no chip, nothing runs) and print what the compiler's memory_analysis()
says, so that a cell's sizes are confirmed before its first chip run
(on-chip-measurement guide, section 2.3).  Run by hand in the sandbox:

    JAX_PLATFORMS=cpu python benchmark/tools/compile_sizes.py [cell ...]

Writes chiprun_out/compile_sizes.json.  Not part of a run."""

from __future__ import annotations

import functools
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _on(tree, sharding):
    import jax

    if not isinstance(sharding, jax.sharding.Sharding):
        return jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=sh),
            tree, sharding)
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _facts(compiled, t0) -> dict:
    m = compiled.memory_analysis()
    text = compiled.as_text()
    gb = 1e9
    return {"argument_GB": m.argument_size_in_bytes / gb,
            "temp_GB": m.temp_size_in_bytes / gb,
            "output_GB": m.output_size_in_bytes / gb,
            "alias_GB": m.alias_size_in_bytes / gb,
            "total_GB": (m.argument_size_in_bytes + m.temp_size_in_bytes
                         + m.output_size_in_bytes
                         - m.alias_size_in_bytes) / gb,
            "total_GiB": (m.argument_size_in_bytes + m.temp_size_in_bytes
                          + m.output_size_in_bytes
                          - m.alias_size_in_bytes) / 2 ** 30,
            "tpu_custom_call": text.count("tpu_custom_call"),
            "collectives": {k: text.count(f" {k}(") + text.count(
                f" {k}-start(") for k in (
                    "all-reduce", "all-gather", "all-to-all",
                    "collective-permute", "reduce-scatter")},
            "compile_s": time.time() - t0}


def train(cell, topo) -> dict:
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                              SingleDeviceSharding)

    from benchmark.harness.families import family_of
    from ray_tpu.parallel.partition_rules import tree_shardings
    from ray_tpu.train import distributed as dist
    from ray_tpu.train.train_step import (TrainState, make_optimizer,
                                          make_sharded_train_step)

    fam, tr = family_of(cell.config), cell.traffic
    plain = fam.program_config(cell.config,
                               attn_impl=tr["step"]["attn_impl"],
                               remat=tr["step"]["remat"])
    m = cell.settings["mesh"]
    optimizer = make_optimizer(**tr["step"]["optimizer"])
    state = jax.eval_shape(lambda: TrainState.create(
        fam.init(plain, jax.random.PRNGKey(0)), optimizer))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (tr["global_batch"], tr["seq_len"] + 1), jnp.int32)}
    t0 = time.time()
    if cell.chips == 1:
        one = SingleDeviceSharding(topo.devices[0])
        step = make_sharded_train_step(
            lambda p, b: fam.loss(plain, p, b,
                                  loss_chunk=tr["step"]["loss_chunk"]),
            optimizer, telemetry=False)
        compiled = step.lower(_on(state, one), _on(batch, one)).compile()
    else:
        mesh = Mesh(np.array(topo.devices).reshape(m["fsdp"], m["tensor"]),
                    ("fsdp", "tensor"))
        cfg = dataclasses.replace(plain, mesh=mesh)
        specs = dist.fitted_state_specs(
            state, mesh, dist.rules_for_model(fam.partition_rules))
        shardings = tree_shardings(mesh, specs)
        bs = NamedSharding(mesh, PartitionSpec("fsdp"))
        step = make_sharded_train_step(
            lambda p, b: fam.loss(cfg, p, b,
                                  loss_chunk=tr["step"]["loss_chunk"]),
            optimizer, mesh=mesh, state_shardings=shardings,
            batch_sharding=bs, telemetry=False)
        compiled = step.lower(_on(state, shardings),
                              _on(batch, bs)).compile()
    return {"train_step": _facts(compiled, t0)}


def serve(cell, topo) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark.harness.families import family_of
    from benchmark.harness.traffic import prefill_buckets
    from ray_tpu.llm.engine import jit_forward
    from ray_tpu.llm.kv_cache import init_cache, pages_for
    from ray_tpu.models.gpt2 import GPT2

    fam, eng = family_of(cell.config), cell.settings["engine"]
    cfg = fam.program_config(cell.config, attn_impl="dense", remat=False)
    one = SingleDeviceSharding(topo.devices[0])
    params = jax.eval_shape(lambda: fam.init(cfg, jax.random.PRNGKey(0)))
    kv = jax.eval_shape(lambda: init_cache(
        cfg.n_layer, eng["num_pages"], eng["page_size"], cfg.n_head,
        cfg.d_model // cfg.n_head, cfg.dtype))
    ints = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                             sharding=one)
    out = {}
    shapes = [("decode", (eng["max_batch"], 1))] + [
        (f"prefill[{b}]", (1, b))
        for b in prefill_buckets(cell.traffic)[-1:]]
    for name, shape in shapes:
        t0 = time.time()
        compiled = jit_forward(GPT2(cfg)).lower(
            _on(params, one), ints(shape), _on(kv["k_pages"], one),
            _on(kv["v_pages"], one),
            ints((shape[0], pages_for(cfg.max_seq, eng["page_size"]))),
            ints(shape)).compile()
        out[name] = _facts(compiled, t0)
    return out


def main(argv) -> None:
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    import ray_tpu.ops
    from benchmark.harness import manifest
    from ray_tpu.ops.flash_attention import flash_attention

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # The model asks jax.default_backend() and would take its interpret
    # branch here: hand it the compiled kernel, as the backend tpu would.
    ray_tpu.ops.flash_attention = functools.partial(flash_attention,
                                                    interpret=False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    names = argv or [w["name"] for w in
                     manifest.load_manifest()["workloads"]]
    path = os.path.join(ROOT, "chiprun_out", "compile_sizes.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    results = json.load(open(path)) if os.path.exists(path) else {}
    for name in names:
        cell = manifest.load_cell(name)
        try:
            res = (train if cell.kind == "train" else serve)(cell, topo)
        except Exception as e:  # noqa: BLE001 — what the compiler refuses
            res = {"error": repr(e)[:2000]}
        results[name] = res
        print(name, json.dumps(res, indent=1), flush=True)
        with open(path, "w") as f:
            json.dump(results, f, indent=1)


if __name__ == "__main__":
    main(sys.argv[1:])
