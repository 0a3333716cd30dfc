"""The training cells: ``JaxTrainer.fit`` with one worker that leases the
cell's chips, ``ray_tpu.data`` -> ``train.iter_device_batches`` ->
``make_sharded_train_step``, ``train.report`` every step.

``train_loop`` is the benchmark's own code shipped into the worker (a copy
of chip_smoke.py's loop, sized and timed as a cell).  The harness process
never starts a JAX backend.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import math
import os
import time
from typing import Any, Dict

from . import cluster, flops, sketch
from .cluster import BenchFailure, log
from .families import family_of
from .manifest import Cell

HOST_SPANS = ("next_batch", "dispatch", "sync", "report")


def _block_source(spec: Dict[str, Any], index: int):
    """A data block made where it is read, from the seed: rows of
    ``seq_len + 1`` token ids, Zipf-distributed over the vocabulary."""
    def src():
        import numpy as np

        d = spec["traffic"]["data"]
        rng = np.random.default_rng([spec["seed"], 0x6462, index])
        shape = (d["rows_per_block"], spec["traffic"]["seq_len"] + 1)
        ranks = rng.zipf(d["zipf_a"], shape) - 1
        perm_seed = np.random.default_rng([spec["seed"], 0x7065])
        vocab = spec["sizes"]["vocab"]
        offset = int(perm_seed.integers(0, vocab))
        return {"tokens": ((ranks + offset) % vocab).astype(np.int32)}
    return src


def check_rows(spec: Dict[str, Any]):
    """The seeded rows the program's loss and the reference's are compared
    on (the same in the worker and in the reference's process)."""
    import numpy as np

    rng = np.random.default_rng([spec["seed"], 0x6368])
    return rng.integers(
        0, spec["sizes"]["vocab"],
        (spec["traffic"]["check"]["rows"],
         spec["traffic"]["seq_len"] + 1)).astype(np.int32)


def run_check_step(step, state, spec: Dict[str, Any], put):
    """The step that is measured, run once from the initial state on the
    seeded check rows, tiled to the global batch so that loss and gradient
    are those of the rows.  Returns the new state and the step's loss and
    gradient norm; writes to ``spec["check_file"]`` a sketch of the first
    and second Adam moments the step leaves, which after one update from
    zero ARE the clipped gradient and its square.  The harness holds all
    of it to the reference's gradients (check.py).  The schedule's rate at
    step 0 is 0, so the weights do not move."""
    import jax
    import numpy as np

    rows = check_rows(spec)
    batch_rows = spec["traffic"]["global_batch"]
    if batch_rows % len(rows):
        raise RuntimeError(f"global_batch {batch_rows} is no multiple of "
                           f"the {len(rows)} check rows")
    tiled = np.tile(rows, (batch_rows // len(rows), 1))
    state, first = step(state, put({"tokens": tiled}))
    moments = sketch.adam_moments(state.opt_state)
    if moments is None:
        raise RuntimeError("no Adam moments (mu, nu) in the optimizer's "
                           "state: the check reads the gradient there")
    np.savez(spec["check_file"], **sketch.flatten(
        jax.jit(sketch.sketch)(moments.mu, moments.nu)))
    return state, {"loss": float(first["loss"]),
                   "grad_norm": float(first["grad_norm"])}


def train_loop(spec: Dict[str, Any]) -> None:
    """Runs in the leased worker."""
    t_entry = time.time()
    import jax

    from ray_tpu import train
    from ray_tpu.train import distributed as dist
    from ray_tpu.parallel.partition_rules import tree_shardings
    from ray_tpu.train.train_step import (TrainState, make_optimizer,
                                          make_sharded_train_step)
    from ray_tpu.util import chips, compile_cache

    cache_dir = compile_cache.apply()
    cache_counts = compile_cache.watch()
    compiles = {"n": 0}

    def on_duration(event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            compiles["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    device = chips.describe_devices()
    if (device["platform"], device["count"]) != (spec["platform"],
                                                 spec["chips"]):
        raise RuntimeError(
            f"the leased train worker found {device}, not {spec['chips']} "
            f"device(s) of platform {spec['platform']!r}; nothing is run "
            "on another backend in its place")

    traffic, fam = spec["traffic"], family_of(spec["config"])
    dm = train.setup_distributed_mesh(**spec["mesh"])
    plain = fam.program_config(spec["config"],
                               attn_impl=traffic["step"]["attn_impl"],
                               remat=traffic["step"]["remat"])
    cfg = dataclasses.replace(plain, mesh=dm.mesh) \
        if dm.mesh.size > 1 else plain
    optimizer = make_optimizer(**traffic["step"]["optimizer"])
    loss_chunk = traffic["step"]["loss_chunk"]

    def loss_fn(params, batch):
        return fam.loss(cfg, params, batch, loss_chunk=loss_chunk)

    # Weights, moments and step counter made on the devices in one jitted
    # call from the seed, each leaf born in the layout the rules give it.
    def create(key):
        return TrainState.create(fam.init(plain, key), optimizer)

    key = jax.random.PRNGKey(spec["seed"])
    specs = dist.fitted_state_specs(
        jax.eval_shape(create, key), dm.mesh,
        train.rules_for_model(fam.partition_rules))
    shardings = tree_shardings(dm.mesh, specs)
    state = jax.jit(create, out_shardings=shardings)(key)
    step = make_sharded_train_step(
        loss_fn, optimizer, mesh=dm.mesh, state_shardings=shardings,
        batch_sharding=dm.batch_sharding())

    # correct, part 1 (the step's first call: it compiles here)
    state, check_step = run_check_step(
        step, state, spec,
        lambda batch: jax.device_put(batch, dm.batch_sharding()))
    batch_rows = traffic["global_batch"]

    shard = train.get_dataset_shard("train")

    def host_batches():
        while True:              # every pass runs the data tasks again
            yield from shard.iter_batches(
                batch_size=batch_rows, batch_format="numpy",
                drop_last=True, prefetch_blocks=2)

    it = train.iter_device_batches(host_batches(),
                                   sharding=dm.batch_sharding())
    tokens_per_step = batch_rows * traffic["seq_len"]
    ann = jax.profiler.TraceAnnotation

    def one_step(state, i):
        t0 = time.perf_counter()
        with ann("next_batch"):
            batch = next(it)
        t1 = time.perf_counter()
        with ann("dispatch"):
            state, metrics = step(state, batch)
        with ann("sync"):
            loss = float(metrics["loss"])    # blocks until the step is done
        t2 = time.perf_counter()
        with ann("report"):
            train.report({"step": i, "loss": loss, "step_s": t2 - t1,
                          "tokens": tokens_per_step})
        t3 = time.perf_counter()
        return state, {"loss": loss, "wait_s": t1 - t0, "step_s": t2 - t1,
                       "report_s": t3 - t2, "wall_s": t3 - t0}

    for i in range(traffic["warmup_steps"]):     # the first one compiles
        state, _ = one_step(state, -1 - i)
    compiles_before = compiles["n"]

    tr = traffic["trace"] if spec["trace"] else None
    records, tracing, window_mark = [], False, None
    setup_done = time.time()
    t_start = time.perf_counter()
    while True:
        n = len(records)
        if tr and n == tr["skip_steps"]:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(spec["trace_dir"],
                                     profiler_options=options)
            window_mark = ann("bench_window")
            window_mark.__enter__()
            tracing = True
        state, rec = one_step(state, n)
        rec["traced"] = tracing
        records.append(rec)
        if tracing and n + 1 == tr["skip_steps"] + tr["steps"]:
            window_mark.__exit__(None, None, None)
            jax.profiler.stop_trace()
            tracing = False
        if not tracing and time.perf_counter() - t_start >= spec["seconds"]:
            break
    window_s = time.perf_counter() - t_start
    it.close()

    compiled = step.compiled()
    mem = compiled.memory_analysis()
    train.report({"step": len(records), "summary": {
        "device": device,
        "mesh": dm.axis_sizes,
        "t_entry": t_entry,
        "setup_done": setup_done,
        "window_s": window_s,
        "tokens_per_step": tokens_per_step,
        "records": records,
        "check_step": check_step,
        "compile_s": step.compile_seconds,
        "compile_cache": {"dir": cache_dir, **cache_counts},
        "compiles_in_window": compiles["n"] - compiles_before,
        "kernel_in_hlo": "tpu_custom_call" in compiled.as_text(),
        "program_bytes": {"argument": mem.argument_size_in_bytes,
                          "temp": mem.temp_size_in_bytes,
                          "output": mem.output_size_in_bytes,
                          "alias": mem.alias_size_in_bytes},
        "allocator_peak_bytes": chips.peak_device_memory_bytes(),
    }})


def run(cell: Cell, args, t_process: float, out_dir: str) -> Dict[str, Any]:
    """The harness's side of a training cell.  Returns the run context the
    metrics are read from."""
    from ray_tpu import data as rt_data
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    fam = family_of(cell.config)
    sizes = fam.sizes(cell.config)
    trace_dir = os.path.join(out_dir, "trace")
    spec = {"seed": int(args.seed), "seconds": float(args.seconds),
            "trace": bool(args.trace), "trace_dir": trace_dir,
            "check_file": os.path.join(out_dir, "check_program.npz"),
            "config": cell.config, "traffic": cell.traffic,
            "sizes": sizes, "mesh": cell.settings["mesh"],
            "chips": cell.chips, "platform": cluster.PLATFORM}
    data = cell.traffic["data"]
    dataset = rt_data.Dataset([_block_source(spec, i)
                               for i in range(data["blocks"])])
    t_init = time.time()
    rt = cluster.start()
    failure, result = None, None
    try:
        trainer = JaxTrainer(
            train_loop, train_loop_config=spec,
            scaling_config=ScalingConfig(
                num_workers=1, use_tpu=True,
                resources_per_worker={"CPU": 1, "TPU": cell.chips}),
            run_config=RunConfig(name="bench_" + cell.name,
                                 storage_path=os.path.join(out_dir, "fit")),
            datasets={"train": dataset})
        result = trainer.fit()
        if result.error is not None:
            raise BenchFailure(f"fit() failed: {result.error!r}") \
                from result.error
    except BaseException as e:  # noqa: BLE001 — reported after shutdown
        failure = e
        cluster.keep_session_logs(rt.session,
                                  os.path.join(out_dir, "failure_logs"))
    finally:
        left = cluster.stop(rt.session)
    if failure is not None:
        raise failure
    if left:
        raise BenchFailure(f"processes left behind: {left}")

    history = result.metrics_history
    summary = history[-1]["metrics"]["summary"]
    records = summary["records"]
    losses = [r["loss"] for r in records]
    with open(os.path.join(out_dir, "losses.json"), "w") as f:
        json.dump({"seed": spec["seed"], "losses": losses,
                   "check_step": summary["check_step"]}, f)
    log(f"train: {len(records)} steps in {summary['window_s']:.2f} s, "
        f"median step {sorted(r['step_s'] for r in records)[len(records)//2]*1e3:.1f} ms, "
        f"compile {summary['compile_s']:.1f} s, cache "
        f"{summary['compile_cache']}, program bytes "
        f"{summary['program_bytes']}, allocator peak "
        f"{summary['allocator_peak_bytes']}")

    problems = []
    if len(history) != len(records) + cell.traffic["warmup_steps"] + 1:
        problems.append(f"{len(history)} reports for {len(records)} "
                        "measured steps")
    if not all(math.isfinite(x) for x in losses):
        problems.append("a loss in the window is not finite")
    if summary["compiles_in_window"]:
        problems.append(f"{summary['compiles_in_window']} compilation(s) "
                        "inside the measured window")
    if cluster.PLATFORM == "tpu" and not summary["kernel_in_hlo"]:
        problems.append("no tpu_custom_call in the step that ran")

    tokens = summary["tokens_per_step"] * len(records)
    # The compiler's own total for the step that ran (the allocator's peak
    # leaves program temporaries out on this runtime and is only logged).
    prog = summary["program_bytes"]
    peak = prog["argument"] + prog["output"] - prog["alias"] + prog["temp"]
    step_flops = summary["tokens_per_step"] * flops.train_flops_per_token(
        sizes["n_layer"], sizes["d_model"], sizes["d_ff"], sizes["vocab"],
        cell.traffic["seq_len"])
    return {
        "cell": cell, "kind": "train", "sizes": sizes,
        "device": summary["device"], "problems": problems,
        "attempted": len(records),
        "failed": sum(not math.isfinite(x) for x in losses),
        "memory_peak_bytes": int(peak),
        "e2e": {"train_tokens_per_s": tokens / summary["window_s"],
                "setup_s": summary["setup_done"] - t_process},
        "times": {"gang_start_s": summary["t_entry"] - t_init},
        "train": {"records": records, "window_s": summary["window_s"],
                  "step_flops": step_flops,
                  "tokens_per_step": summary["tokens_per_step"],
                  "check_step": summary["check_step"],
                  "check_file": spec["check_file"]},
        "trace_path": next(iter(glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb"))), None)
        if args.trace else None,
        "host_spans": HOST_SPANS, "default_host": "train_loop",
    }
