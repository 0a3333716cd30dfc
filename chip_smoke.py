#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls, at
the full width of GPT-2 124M (12 layers, d_model 768, 12 heads, d_ff 3072,
vocab 50257, seq 1024, bf16), with random weights made from a seed:

  train   ray_tpu.init(mode="cluster") -> JaxTrainer(...).fit(): one worker
          that leases the chip; ray_tpu.data -> train.iter_device_batches ->
          setup_distributed_mesh -> shard_train_state ->
          make_sharded_train_step (flash kernel, remat, chunked loss,
          AdamW, donated state); train.report every step.
  serve   serve.run(llm_deployment(...)): one replica that leases the chip;
          seeded requests over the handle and over the HTTP proxy.

With --four-chip it runs instead, and only, the sharded trainer on all
four chips of a 2x2 host (one worker, mesh fsdp=2 x tensor=2) beside the
same steps unsharded on one of that worker's devices.

This process stays off the chip: it never initialises a JAX backend.  The
model, the optimizer state and the data are made inside the train loop
and inside the replica.  Each phase prints JSON lines; any failure makes
the exit code non-zero.  The LAST line of stdout is exactly

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as the worker and the replica reported it.  Where there is
no TPU it fails, saying so, and runs nothing on the CPU in its place.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import os
import sys
import tempfile
import threading
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))

GPT2_124M = dict(n_layer=12, n_head=12, d_model=768, d_ff=3072,
                 vocab_size=50257, max_seq=1024)


class SmokeFailure(RuntimeError):
    """A phase did not meet its pass conditions."""


def one_chip_spec(seed: int = 0) -> dict:
    """Everything the phases are sized and judged by.  The CPU rehearsal
    in tests/test_chip_smoke.py runs the same code on a smaller spec."""
    return {
        "seed": seed,
        "model": dict(GPT2_124M),
        "attn_impl": "flash", "loss_chunk": 256,
        "global_batch": 16, "steps": 8,          # first step compiles
        "data_blocks": 2, "rows_per_block": 16,  # small, repeated
        "token_subset": 256,
        # What the worker and the replica must find.
        "platform": "tpu", "chips": 1, "kernel_marker": "tpu_custom_call",
        "device_nodes": True,
        "mesh": {"fsdp": 1, "tensor": 1}, "compare_unsharded": False,
        "loss_tolerance": 0.0,
        "phases": ["train", "serve"],
        # serve
        "page_size": 16, "num_pages": 1024, "max_batch": 8,
        "prompt_lens": [5, 37, 120, 300, 64], "max_tokens": 32,
        "out_dir": os.path.join(HERE, "chiprun_out", "chip_smoke"),
    }


def four_chip_spec(seed: int = 0) -> dict:
    spec = one_chip_spec(seed)
    spec.update({
        "chips": 4, "mesh": {"fsdp": 2, "tensor": 2},
        "compare_unsharded": True,
        # bf16 params and activations, fp32 loss: per-step losses of the
        # sharded and the unsharded run differ by reduction order only.
        "loss_tolerance": 0.05,
        "phases": ["train"],
    })
    return spec


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


# --------------------------------------------------------------------------
# Code that runs INSIDE the cluster (shipped by value): data blocks, the
# train loop, the chip-less probe task.
# --------------------------------------------------------------------------

def _token_block_source(spec: dict, index: int):
    """A data block made where it is read, from the seed: rows of
    ``max_seq + 1`` tokens drawn from a fixed subset of the vocabulary,
    so that a few steps over the repeated rows lower the loss."""
    def src():
        import numpy as np

        vocab = spec["model"]["vocab_size"]
        subset = np.random.default_rng(spec["seed"]).choice(
            vocab, size=min(spec["token_subset"], vocab), replace=False)
        rng = np.random.default_rng(spec["seed"] + 1 + index)
        picks = rng.integers(
            0, len(subset),
            (spec["rows_per_block"], spec["model"]["max_seq"] + 1))
        return {"tokens": subset[picks].astype(np.int32)}
    return src


def _shard_bytes_per_device(tree) -> dict:
    """Bytes of ``tree`` each device holds (from addressable_shards)."""
    import jax

    held: dict = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for shard in leaf.addressable_shards:
            held[shard.device.id] = held.get(shard.device.id, 0) \
                + shard.data.nbytes
    return {str(k): v for k, v in sorted(held.items())}


def train_loop(spec: dict) -> None:
    """The train loop of the `train` phase, run by the leased worker."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu import train
    from ray_tpu.models.gpt2 import GPT2Config, gpt2_init, gpt2_loss_fn
    from ray_tpu.parallel.partition_rules import tree_shardings
    from ray_tpu.train.train_step import (TrainState, make_optimizer,
                                          make_sharded_train_step)
    from ray_tpu.util import chips, compile_cache, xprof

    cache_dir = compile_cache.apply()
    cache_counts = compile_cache.watch()
    device = chips.describe_devices()
    if (device["platform"], device["count"]) != (spec["platform"],
                                                 spec["chips"]):
        raise RuntimeError(
            f"the leased train worker found {device}, not "
            f"{spec['chips']} device(s) of platform {spec['platform']!r}"
            "; nothing is run on another backend in its place")

    dm = train.setup_distributed_mesh(**spec["mesh"])
    plain = GPT2Config(**spec["model"], attn_impl=spec["attn_impl"],
                       remat=True, dtype=jnp.bfloat16)
    # Across several chips the model is told the mesh: its kernel then
    # runs per shard (a Mosaic kernel is not partitioned automatically).
    cfg = dataclasses.replace(plain, mesh=dm.mesh) \
        if dm.mesh.size > 1 else plain
    optimizer = make_optimizer(learning_rate=1e-3, warmup_steps=2,
                               total_steps=spec["steps"])

    def loss_fn(params, batch, cfg=cfg):
        return gpt2_loss_fn(cfg, params, batch,
                            loss_chunk=spec["loss_chunk"])

    def fresh_state():
        return TrainState.create(
            gpt2_init(plain, jax.random.PRNGKey(spec["seed"])), optimizer)

    state, specs = train.shard_train_state(
        fresh_state(), dm.mesh, train.rules_for_model("gpt2"))
    held = {"params": _shard_bytes_per_device(state.params),
            "opt_state": _shard_bytes_per_device(state.opt_state)}
    step = make_sharded_train_step(
        loss_fn, optimizer, mesh=dm.mesh,
        state_shardings=tree_shardings(dm.mesh, specs),
        batch_sharding=dm.batch_sharding())

    shard = train.get_dataset_shard("train")

    def host_batches():
        while True:                      # the small dataset, repeated
            yield from shard.iter_batches(
                batch_size=spec["global_batch"], batch_format="numpy",
                drop_last=True, prefetch_blocks=1)

    tokens_per_step = spec["global_batch"] * spec["model"]["max_seq"]
    replay, losses, step_s = [], [], []
    batches = host_batches()

    def recorded():
        for b in batches:
            replay.append({k: np.asarray(v) for k, v in b.items()})
            yield b

    it = train.iter_device_batches(recorded(),
                                   sharding=dm.batch_sharding())
    for i in range(spec["steps"]):
        batch = next(it)
        t0 = time.perf_counter()
        state, metrics = step(state, batch)
        loss = float(metrics["loss"])        # blocks until the step is done
        dt = time.perf_counter() - t0
        losses.append(loss)
        step_s.append(dt)
        train.report({"step": i, "loss": loss, "step_s": dt,
                      "tokens": tokens_per_step})
    it.close()

    compiled = step.compiled()
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    prog = xprof.local_programs().get("train_step") or {}
    summary = {
        "device": device,
        "mesh": dm.axis_sizes,
        "compile_s": step.compile_seconds,
        "compile_cache": {"dir": cache_dir, **cache_counts},
        "losses": losses,
        "step_s": step_s[1:],
        "kernel_in_hlo": (spec["kernel_marker"] in text
                          if spec["kernel_marker"] else None),
        "aot_executable_ran": compiled is not None,
        "program_bytes": {
            "argument": mem.argument_size_in_bytes,
            "temp": mem.temp_size_in_bytes,
            "output": mem.output_size_in_bytes},
        "collective_bytes_by_axis": {
            axis: a.get("bytes", 0.0)
            for axis, a in (prog.get("collectives") or {}).items()},
        "bytes_held_per_device": held,
        "peak_hbm_bytes": chips.peak_device_memory_bytes(),
    }

    if spec["compare_unsharded"]:
        # Same seed, same batches, one of this worker's devices.
        del state, batch, metrics
        one = jax.devices()[0]
        ref_state = jax.device_put(fresh_state(), one)
        ref_step = make_sharded_train_step(
            functools.partial(loss_fn, cfg=plain), optimizer)
        ref_losses = []
        for host in replay[:spec["steps"]]:
            ref_state, m = ref_step(ref_state, jax.device_put(host, one))
            ref_losses.append(float(m["loss"]))
        summary["unsharded_losses"] = ref_losses
        summary["unsharded_compile_s"] = ref_step.compile_seconds

    train.report({"step": spec["steps"], "summary": summary})


def _cpu_jax_probe() -> dict:
    """A task with NO chip lease that imports and uses jax."""
    import jax
    import jax.numpy as jnp

    x = jnp.ones((256, 256), jnp.float32)
    return {"pid": os.getpid(),
            "platform": jax.devices()[0].platform,
            "trace": float(jnp.trace(x @ x)),
            "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS")}


# --------------------------------------------------------------------------
# The parent's side: who holds the chip.
# --------------------------------------------------------------------------

def device_node_holders() -> dict:
    """{pid: [device nodes]} of every process that has a chip's device
    node open (/dev/accel*, /dev/vfio/*), read from /proc."""
    holders: dict = {}
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            fds = os.listdir(f"/proc/{pid}/fd")
        except OSError:
            continue
        nodes = set()
        for fd in fds:
            try:
                target = os.readlink(f"/proc/{pid}/fd/{fd}")
            except OSError:
                continue
            if target.startswith(("/dev/accel", "/dev/vfio/")):
                nodes.add(target)
        if nodes:
            holders[int(pid)] = sorted(nodes)
    return holders


def chip_lease_pids() -> dict:
    """{worker pid: chip ids} of the leases that hold chips, from the
    node agents' lease ledgers."""
    from ray_tpu.util import state

    out = {}
    for node in state.list_leases():
        for lease in node.get("leases") or []:
            if lease.get("chip_ids"):
                out[int(lease["worker_pid"])] = list(lease["chip_ids"])
    return out


def check_chip_owner(spec: dict, phase: str, reported_pid=None,
                     wait_s: float = 180.0) -> dict:
    """Exactly one process has the chip: the one that holds the lease.
    Then a chip-less task that uses jax runs beside it on the CPU
    backend, and the owner is unchanged."""
    import ray_tpu

    deadline = time.time() + wait_s
    leases, holders = {}, {}
    while time.time() < deadline:
        leases = chip_lease_pids()
        holders = device_node_holders() if spec["device_nodes"] else {}
        if leases and (holders or not spec["device_nodes"]):
            break
        time.sleep(0.25)
    probe = ray_tpu.get(
        ray_tpu.remote(num_cpus=0)(_cpu_jax_probe).remote(), timeout=120)
    after = device_node_holders() if spec["device_nodes"] else {}
    out = {"phase": phase, "check": "chip_owner",
           "lease_pids": {str(k): v for k, v in leases.items()},
           "device_node_holders": {str(k): v for k, v in holders.items()},
           "holders_after_probe": sorted(after),
           "reported_pid": reported_pid, "chipless_task": probe}
    problems = []
    if len(leases) != 1:
        problems.append(f"{len(leases)} chip leases, wanted 1")
    elif len(next(iter(leases.values()))) != spec["chips"]:
        problems.append(f"lease holds {leases}, wanted "
                        f"{spec['chips']} chip(s)")
    if spec["device_nodes"] and set(holders) != set(leases):
        problems.append("the device nodes are open in pids "
                        f"{sorted(holders)}, the lease is held by "
                        f"{sorted(leases)}")
    if spec["device_nodes"] and set(after) != set(holders):
        problems.append("the chip's holders changed while a chip-less "
                        f"task ran: {sorted(holders)} -> {sorted(after)}")
    if reported_pid is not None and set(leases) != {reported_pid}:
        problems.append(f"pid {reported_pid} reported the device, the "
                        f"lease is held by {sorted(leases)}")
    if probe["platform"] != "cpu" or probe["pid"] in leases:
        problems.append(f"the chip-less task ran as {probe}")
    out["ok"] = not problems
    out["problems"] = problems
    emit(out)
    if problems:
        raise SmokeFailure(f"{phase}: chip ownership: {problems}")
    return out


def wait_chip_free(spec: dict, wait_s: float = 60.0) -> None:
    import ray_tpu

    deadline = time.time() + wait_s
    while time.time() < deadline:
        free = ray_tpu.available_resources().get("TPU", 0.0)
        holders = device_node_holders() if spec["device_nodes"] else {}
        if free >= spec["chips"] and not chip_lease_pids() and not holders:
            return
        time.sleep(0.25)
    raise SmokeFailure(
        f"the chip was not released: TPU available "
        f"{ray_tpu.available_resources().get('TPU')}, leases "
        f"{chip_lease_pids()}, device nodes {device_node_holders()}")


# --------------------------------------------------------------------------
# Phases.
# --------------------------------------------------------------------------

def phase_train(spec: dict) -> dict:
    from ray_tpu import data as rt_data
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    dataset = rt_data.Dataset([_token_block_source(spec, i)
                               for i in range(spec["data_blocks"])])
    trainer = JaxTrainer(
        train_loop, train_loop_config=spec,
        scaling_config=ScalingConfig(
            num_workers=1, use_tpu=True,
            resources_per_worker={"CPU": 1, "TPU": spec["chips"]}),
        run_config=RunConfig(name="chip_smoke_train",
                             storage_path=spec["out_dir"]),
        datasets={"train": dataset})

    owner: dict = {}

    def watch():
        try:
            owner["check"] = check_chip_owner(spec, "train")
        except Exception as e:  # noqa: BLE001 — re-raised below
            owner["error"] = e

    watcher = threading.Thread(target=watch, name="chip-owner-check",
                               daemon=True)
    t0 = time.time()
    watcher.start()
    result = trainer.fit()
    watcher.join(timeout=300)
    if result.error is not None:
        raise SmokeFailure(f"train: fit() failed: {result.error!r}") \
            from result.error
    if "error" in owner:
        raise owner["error"]
    if "check" not in owner:
        raise SmokeFailure("train: the chip-owner check never finished")

    history = result.metrics_history
    summary = history[-1]["metrics"]["summary"]
    losses = summary["losses"]
    steady = summary["step_s"]
    median_s = sorted(steady)[len(steady) // 2]
    line = {
        "phase": "train", "wall_s": round(time.time() - t0, 2),
        "device": summary["device"], "mesh": summary["mesh"],
        "compile_s": summary["compile_s"],
        "compile_cache": summary["compile_cache"],
        "steps": len(losses), "losses": losses,
        "step_s_median": median_s,
        "smoke_tokens_per_s": spec["global_batch"]
        * spec["model"]["max_seq"] / median_s,
        "kernel_in_hlo": summary["kernel_in_hlo"],
        "aot_executable_ran": summary["aot_executable_ran"],
        "program_bytes": summary["program_bytes"],
        "peak_hbm_bytes": summary["peak_hbm_bytes"],
        "bytes_held_per_device": summary["bytes_held_per_device"],
        "collective_bytes_by_axis": summary["collective_bytes_by_axis"],
    }
    problems = []
    if summary["device"]["pid"] not in map(
            int, owner["check"]["lease_pids"]):
        problems.append("the worker that reported the device "
                        f"(pid {summary['device']['pid']}) is not the "
                        f"lease holder {owner['check']['lease_pids']}")
    if len(losses) != spec["steps"] \
            or len(history) != spec["steps"] + 1:
        problems.append(f"{len(losses)} steps / {len(history)} reports, "
                        f"wanted {spec['steps']}")
    if not all(map(math.isfinite, losses)):
        problems.append(f"non-finite loss in {losses}")
    elif not losses[-1] < losses[0]:
        problems.append(f"loss did not fall: {losses[0]} -> {losses[-1]}")
    if summary["kernel_in_hlo"] is False:
        problems.append(f"no {spec['kernel_marker']} in the compiled "
                        "step: the kernel did not compile into it")
    if not summary["aot_executable_ran"]:
        problems.append("the step did not run its AOT executable")

    if spec["chips"] > 1:
        total = {k: sum(v.values()) for k, v in
                 summary["bytes_held_per_device"].items()}
        for kind, held in summary["bytes_held_per_device"].items():
            if len(held) != spec["chips"] or min(held.values()) <= 0:
                problems.append(f"{kind}: not every device holds a "
                                f"shard: {held}")
            elif max(held.values()) > 0.6 * total[kind]:
                problems.append(f"{kind}: one device holds most of "
                                f"it: {held}")
        axes = summary["collective_bytes_by_axis"]
        for axis, size in spec["mesh"].items():
            if size > 1 and not sum(
                    b for a, b in axes.items() if axis in a) > 0:
                problems.append(f"no collective on the {axis} axis in "
                                f"the compiled step: {axes}")
    if spec["compare_unsharded"]:
        ref = summary["unsharded_losses"]
        diffs = [abs(a - b) for a, b in zip(losses, ref)]
        line["unsharded_losses"] = ref
        line["max_abs_loss_diff"] = max(diffs)
        line["loss_tolerance"] = spec["loss_tolerance"]
        if len(ref) != len(losses) \
                or max(diffs) > spec["loss_tolerance"]:
            problems.append(
                f"sharded and unsharded losses disagree by "
                f"{max(diffs)} (> {spec['loss_tolerance']})")
    line["ok"] = not problems
    line["problems"] = problems
    emit(line)
    if problems:
        raise SmokeFailure(f"train: {problems}")
    return summary["device"]


def _prompts(spec: dict) -> list:
    import numpy as np

    rng = np.random.default_rng(spec["seed"] + 100)
    vocab = spec["model"]["vocab_size"]
    return [rng.integers(0, vocab, n).tolist()
            for n in spec["prompt_lens"]]


def _collect(frames, spec: dict, what: str) -> list:
    tokens, done = [], None
    for fr in frames:
        if "error" in fr:
            raise SmokeFailure(f"serve: {what}: error frame {fr}")
        if "token" in fr:
            tokens.append(int(fr["token"]))
        if "done" in fr:
            done = fr
    if len(tokens) != spec["max_tokens"] or done is None:
        raise SmokeFailure(
            f"serve: {what}: {len(tokens)} token frames (wanted "
            f"{spec['max_tokens']}), done frame {done}")
    return tokens


def phase_serve(spec: dict) -> dict:
    import jax.numpy as jnp   # dtype names only: starts no backend

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm import EngineConfig, llm_deployment
    from ray_tpu.models.gpt2 import GPT2Config

    cfg = GPT2Config(**spec["model"], attn_impl="dense", remat=False,
                     dtype=jnp.bfloat16)
    t0 = time.time()
    try:
        handle = serve.run(
            llm_deployment(
                name="llm", model="gpt2", model_cfg=cfg,
                engine_cfg=EngineConfig(
                    page_size=spec["page_size"],
                    num_pages=spec["num_pages"],
                    max_batch=spec["max_batch"]),
                seed=spec["seed"]),
            route_prefix="/llm")
        # stats() waits for the engine (weights, warm-up compiles).
        stats = ray_tpu.get(handle.method("stats").remote(), timeout=900)
        ready_s = time.time() - t0
        device = stats["device"]
        if (device["platform"], device["count"]) != (spec["platform"],
                                                     spec["chips"]):
            raise SmokeFailure(
                f"serve: the replica found {device}, not "
                f"{spec['chips']} device(s) of {spec['platform']!r}")
        check_chip_owner(spec, "serve", reported_pid=device["pid"])

        request_s, outputs = [], []
        for i, prompt in enumerate(_prompts(spec)):
            t1 = time.time()
            outputs.append(_collect(
                handle.stream({"prompt": prompt,
                               "max_tokens": spec["max_tokens"],
                               "temperature": 0.0}),
                spec, f"handle request {i} ({len(prompt)} tokens in)"))
            request_s.append(time.time() - t1)
        again = _collect(
            handle.stream({"prompt": _prompts(spec)[1],
                           "max_tokens": spec["max_tokens"],
                           "temperature": 0.0}),
            spec, "repeated greedy request")
        if again != outputs[1]:
            raise SmokeFailure("serve: the same greedy request gave "
                               f"{outputs[1]} and then {again}")

        port = serve.start_http_proxy()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/llm",
            data=json.dumps({"prompt": _prompts(spec)[0],
                             "max_tokens": spec["max_tokens"],
                             "temperature": 0.0}).encode(),
            headers={"Content-Type": "application/json"})
        t1 = time.time()
        with urllib.request.urlopen(req, timeout=300) as resp:
            http_tokens = _collect(
                (json.loads(line) for line in resp if line.strip()),
                spec, "http request")
        http_s = time.time() - t1
        if http_tokens != outputs[0]:
            raise SmokeFailure("serve: http and handle disagree on the "
                               "same greedy request")

        stats = ray_tpu.get(handle.method("stats").remote(), timeout=60)
        line = {
            "phase": "serve", "wall_s": round(time.time() - t0, 2),
            "device": device, "ready_s": round(ready_s, 2),
            "compile_s": stats["programs"],
            "compile_cache": stats.get("compile_cache"),
            "requests": len(request_s) + 2,
            "tokens_out_each": spec["max_tokens"],
            "prompt_lens": spec["prompt_lens"],
            "request_s": request_s, "http_request_s": http_s,
            # One stream at a time, fastest request (its prefill bucket
            # was already compiled): a smoke number.
            "smoke_tokens_per_s_one_stream": spec["max_tokens"]
            / min(request_s),
            "step_errors": stats["step_errors"],
            "last_error": stats["last_error"],
            "tokens_generated": stats["tokens_generated"],
            "kv_pages_total": stats["kv_pages_total"],
            "peak_hbm_bytes": stats.get("peak_hbm_bytes"),
        }
        problems = []
        if stats["step_errors"] or stats["last_error"]:
            problems.append(f"engine step errors: {stats['step_errors']}"
                            f", last {stats['last_error']}")
        if stats["kv_pages_used"]:
            problems.append(f"{stats['kv_pages_used']} KV pages still "
                            "held after every request finished")
        line["ok"] = not problems
        line["problems"] = problems
        emit(line)
        if problems:
            raise SmokeFailure(f"serve: {problems}")
        return device
    finally:
        serve.shutdown()


# --------------------------------------------------------------------------
# The run.
# --------------------------------------------------------------------------

def session_processes(session: str) -> list:
    """Live processes of the cluster session (its name is in the command
    line of the controller and the agent, and in the workers'
    environment)."""
    found = []
    for pid in os.listdir("/proc"):
        if not pid.isdigit() or int(pid) == os.getpid():
            continue
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmd = f.read().replace(b"\0", b" ").decode(errors="replace")
            with open(f"/proc/{pid}/environ", "rb") as f:
                env = f.read().decode(errors="replace")
            with open(f"/proc/{pid}/stat") as f:
                zombie = f.read().rsplit(")", 1)[1].split()[0] == "Z"
        except OSError:
            continue
        if zombie:
            continue
        if session in cmd or f"RT_SESSION_NAME={session}\0" in env:
            found.append({"pid": int(pid), "cmd": cmd.strip()[:120]})
    return found


def keep_session_logs(session_dir: str, out_dir: str) -> str:
    """After a failure: the end of every log of the session, kept where
    the chip tool brings files back from."""
    dest = os.path.join(out_dir, "failure_logs")
    os.makedirs(dest, exist_ok=True)
    logs = os.path.join(session_dir, "logs")
    for name in sorted(os.listdir(logs) if os.path.isdir(logs) else []):
        try:
            with open(os.path.join(logs, name), "rb") as f:
                f.seek(0, os.SEEK_END)
                f.seek(max(f.tell() - 65536, 0))
                tail = f.read()
            with open(os.path.join(dest, name), "wb") as f:
                f.write(tail)
        except OSError:
            continue
    return dest


def liveness_events(session_dir: str) -> list:
    """What the controller logged about stalls and lost nodes: a TPU
    runtime starting up can make the whole machine stand still."""
    try:
        with open(os.path.join(session_dir, "logs", "controller.log"),
                  errors="replace") as f:
            return [line.strip()[:200] for line in f
                    if "late" in line or "dead" in line]
    except OSError:
        return []


def run(spec: dict, *, num_tpus=None, num_cpus=None) -> int:
    """Start the cluster, run the spec's phases, shut down, print the
    result line.  Returns the exit code."""
    try:
        import ray_tpu
        from ray_tpu import _native
        from ray_tpu.core.resources import detect_tpu
        from ray_tpu.util import compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the runtime: {e!r}",
              file=sys.stderr)
        return 2

    info = detect_tpu()
    have = num_tpus if num_tpus is not None else \
        (info.num_chips if info else 0)
    if have < spec["chips"]:
        print(f"chip_smoke: found no TPU to run on: this host shows "
              f"{have} chip(s) (device nodes /dev/accel*, /dev/vfio/<n>)"
              f", the run needs {spec['chips']}; nothing is run on the "
              "CPU in its place", file=sys.stderr)
        return 3

    session_root = os.path.join(tempfile.gettempdir(), "ray_tpu")
    cache_from_outside = compile_cache.ENV in os.environ
    cache_dir = compile_cache.ensure_env()
    cache_warm = os.path.isdir(cache_dir) and bool(os.listdir(cache_dir))
    rt = ray_tpu.init(
        mode="cluster", num_cpus=num_cpus, num_tpus=num_tpus,
        config={"object_store_backend": "pool",
                "session_dir_root": session_root})
    session = rt.session
    devices, failure = [], None
    try:
        emit({"phase": "start", "session": session,
              "node_tpus": ray_tpu.cluster_resources().get("TPU", 0.0),
              "detected": vars(info) if info else None,
              "object_store": type(rt.store).__name__,
              "native_pool_built": bool(
                  _native.build_library("shm_pool.cpp")),
              "compile_cache": {"dir": cache_dir,
                                "placed_from_outside": cache_from_outside,
                                "warm": cache_warm},
              "phases": spec["phases"]})
        for name in spec["phases"]:
            if devices:
                wait_chip_free(spec)
            devices.append(
                {"train": phase_train, "serve": phase_serve}[name](spec))
    except Exception as e:  # noqa: BLE001 — reported, then exit != 0
        failure = e
        print("chip_smoke: session logs kept in " + keep_session_logs(
            os.path.join(session_root, session), spec["out_dir"]),
            file=sys.stderr)
    finally:
        ray_tpu.shutdown()

    deadline = time.time() + 30
    left = session_processes(session)
    while left and time.time() < deadline:
        time.sleep(0.5)
        left = session_processes(session)
    emit({"phase": "shutdown", "processes_left": left,
          "liveness_events": liveness_events(
              os.path.join(session_root, session))})
    if failure is not None:
        import traceback

        traceback.print_exception(failure, file=sys.stderr)
        print(f"chip_smoke: FAILED: {failure}", file=sys.stderr)
        return 1
    if left:
        print(f"chip_smoke: FAILED: processes left behind: {left}",
              file=sys.stderr)
        return 1
    kinds = {(d["platform"], d["kind"], d["count"]) for d in devices}
    if len(kinds) != 1:
        print(f"chip_smoke: FAILED: phases disagree on the device: "
              f"{devices}", file=sys.stderr)
        return 1
    platform, kind, count = kinds.pop()
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": count}}), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the sharded trainer on all four chips "
                         "of a 2x2 host, beside the unsharded steps")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    spec = four_chip_spec(args.seed) if args.four_chip \
        else one_chip_spec(args.seed)
    return run(spec)


if __name__ == "__main__":
    sys.exit(main())
