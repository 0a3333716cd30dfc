"""The serving cells: ``serve.run(llm_deployment(...))``, one replica that
leases the chip, requests over ``handle.stream`` from this process's load
generator.  The harness process never starts a JAX backend; the replica is
not reached into: what is known of it comes from its ``stats()``, the
cluster's telemetry and, in a traced run, a profiler capture
(traced_replica.py)."""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import threading
import time
from typing import Any, Dict, List

import numpy as np

from . import client, cluster, traffic as traffic_mod
from .cluster import BenchFailure, log
from .families import family_of
from .manifest import Cell
from .stats import percentile

TRACE_SECONDS = 3.0
# The capture's whole budget, from its start to the RPC's return: the
# TRACE_SECONDS and the export of the .xplane.pb inside the replica, beside
# the engine (~1.1 s a MB of trace: 140-152 s in
# serve-kimi-linear-48b-a3b-longout, PERF.md section 7).  The wait after the
# window follows it; there is no second, smaller limit.  The margin is the
# timer's own start and the RPC's way back.
CAPTURE_BUDGET_S = 300.0
CAPTURE_MARGIN_S = 15.0
SNAPSHOT_WAIT_S = 120.0


def _warmup(handle, cell: Cell, sizes: Dict[str, int], seed: int) -> None:
    """One ``"warmup": true`` request per prefill bucket the traffic's
    prompt lengths can reach (the decode program and bucket 8 are the
    replica's own warm-up), then one greedy request asked twice."""
    rng = np.random.default_rng([seed, 0x7775])
    longest = cell.traffic["prompt_len"]["max"]
    for bucket in traffic_mod.prefill_buckets(cell.traffic):
        n = min(bucket, longest)
        frames = list(handle.stream({
            "prompt": rng.integers(0, sizes["vocab"], n).tolist(),
            "max_tokens": 2, "temperature": 0.0, "warmup": True}))
        if any("error" in f for f in frames) or not any(
                "done" in f for f in frames):
            raise BenchFailure(f"warm-up of prefill bucket {bucket} "
                               f"failed: {frames[-1:]}")
    probe = {"index": -1, "greedy": True, "payload": {
        "prompt": rng.integers(0, sizes["vocab"], 48).tolist(),
        "max_tokens": 16, "temperature": 0.0}}
    a = client.stream_one(handle, probe)
    b = client.stream_one(handle, probe)
    if not (a["ok"] and b["ok"]) or a["tokens"] != b["tokens"]:
        raise BenchFailure("one greedy request asked twice gave "
                           f"{a['tokens']} ({a['error']}) and then "
                           f"{b['tokens']} ({b['error']})")


def xplane_under(log_dir: str):
    """The capture's ``.xplane.pb`` under ``log_dir``, or None."""
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    return found[0] if found else None


def no_xplane_under(log_dir: str) -> str:
    """For a failure's text: where no capture lay, and what did."""
    lay = sorted(os.path.relpath(os.path.join(d, f), log_dir)
                 for d, _, files in os.walk(log_dir) for f in files)
    return (f"no *.xplane.pb under {log_dir}/plugins/profile/*/; there "
            f"lay: {lay or 'nothing'}")


def _still_running(t0: float, budget_s: float) -> str:
    return ("the profiler capture was still running "
            f"{time.perf_counter() - t0:.1f} s after its start (budget "
            f"{budget_s:g} s): the export of the .xplane.pb in the replica "
            "outlives CAPTURE_BUDGET_S")


def _during(out_dir: str, box: Dict[str, Any], handle, seconds: float,
            trace: bool, budget_s: float = CAPTURE_BUDGET_S):
    """``during`` callback of the load generator: the engine's counters
    read at the window's start and end, and in a traced run a few seconds of profiler
    capture in the worker that holds the chip, a third into the window.
    The capture leaves ``box["xplane"]`` with what it cost, or
    ``box["no_capture"]``: why there is none (``_await_timers`` raises it)."""
    def capture() -> None:
        import ray_tpu

        box["t_capture"] = t0 = time.perf_counter()
        try:
            log_dir = ray_tpu.get(handle.method("bench_trace").remote(
                os.path.join(out_dir, "trace"), TRACE_SECONDS),
                timeout=budget_s)
        except ray_tpu.GetTimeoutError:
            box["no_capture"] = _still_running(t0, budget_s)
            return
        except Exception as e:  # noqa: BLE001 — the run then fails
            box["no_capture"] = ("the profiler capture raised after "
                                 f"{time.perf_counter() - t0:.1f} s: {e!r}")
            return
        box["capture_s"] = time.perf_counter() - t0
        path = xplane_under(log_dir)
        if path is None:
            box["no_capture"] = (
                f"the profiler capture returned after {box['capture_s']:.1f}"
                f" s and left {no_xplane_under(log_dir)}")
            return
        box["xplane"], box["xplane_bytes"] = path, os.path.getsize(path)

    def snapshot(key: str):
        def take() -> None:
            import ray_tpu

            try:
                box[key] = ray_tpu.get(
                    handle.method("stats").remote(), timeout=60)
                box["t_" + key] = time.perf_counter()
            except Exception as e:  # noqa: BLE001
                box["error"] = repr(e)
        return take

    def during(t0: float) -> None:
        def timer(after_s: float, fn) -> threading.Timer:
            t = threading.Timer(
                max(after_s - (time.perf_counter() - t0), 0.0), fn)
            t.daemon = True
            t.start()
            return t

        box["timers"] = [timer(0.0, snapshot("at_start")),
                         timer(seconds, snapshot("at_end"))]
        if trace:
            box["capture"] = (timer(seconds / 3.0, capture), budget_s)
    return during


def _await_timers(box: Dict[str, Any],
                  margin_s: float = CAPTURE_MARGIN_S) -> None:
    """After the window and the drain: the two snapshots' short wait, and
    for the capture what is left of its budget, and the margin.  A traced
    run that ends here without its trace says which of three things
    happened."""
    for timer in box["timers"]:
        timer.join(timeout=SNAPSHOT_WAIT_S)
    if "capture" not in box:
        return
    timer, budget_s = box["capture"]
    started = box.get("t_capture", time.perf_counter())
    timer.join(timeout=max(started + budget_s - time.perf_counter(), 0.0)
               + margin_s)
    if "xplane" not in box:
        raise BenchFailure(box.get("no_capture")
                           or _still_running(started, budget_s))


def _settled_stats(handle, wait_s: float = 3.0) -> Dict[str, Any]:
    """stats() once every stream has ended and the engine has let go of
    the sequences the clients cut (cancellation takes an engine step)."""
    import ray_tpu

    deadline = time.time() + wait_s
    while True:
        stats = ray_tpu.get(handle.method("stats").remote(), timeout=60)
        if not (stats["kv_pages_used"] or stats["running"]
                or stats["waiting"]) or time.time() > deadline:
            return stats
        time.sleep(0.25)


def _xla_memory(prefix: str = "llm_") -> Dict[str, Any]:
    """From the cluster's telemetry: for every program named ``prefix*``
    the compiler's own total (arguments + outputs - aliased + temporaries,
    its ``memory_analysis()``), and the allocator's peak on the fullest
    device, which leaves program temporaries out and is only logged."""
    from ray_tpu.util.telemetry import cluster_summary

    xla = (cluster_summary().get("xla") or {})
    totals = {}
    for name, p in (xla.get("programs") or {}).items():
        m = p.get("memory") or {}
        if name.startswith(prefix) and m.get("temp"):
            totals[name] = (m.get("argument", 0.0) + m.get("output", 0.0)
                            - m.get("alias", 0.0) + m["temp"])
    peaks = [kinds.get("peak", 0.0)
             for devs in (xla.get("device_memory") or {}).values()
             for kinds in devs.values()]
    return {"program_total": totals,
            "allocator_peak": max(peaks) if peaks else 0.0}


def start_replica(cell: Cell, args, fam, seed: int):
    """``serve.run`` of the cell's deployment in the running cluster; waits
    for the engine (weights, the replica's own warm-up) and checks what it
    computes on.  Returns (handle, when serve.run returned, device)."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm import EngineConfig, llm_deployment

    model_cfg = fam.program_config(cell.config, attn_impl="dense",
                                   remat=False)
    app = llm_deployment(name="llm", model=fam.engine_model,
                         model_cfg=model_cfg,
                         engine_cfg=EngineConfig(**cell.settings["engine"]),
                         seed=seed)
    if getattr(args, "trace", 0):
        from .traced_replica import TracedLLMDeployment

        app = dataclasses.replace(app, deployment=app.deployment.options(
            func_or_class=TracedLLMDeployment))
    handle = serve.run(app, route_prefix="/llm")
    t_replica = time.time()
    stats = ray_tpu.get(handle.method("stats").remote(), timeout=1100)
    device = stats["device"]
    if (device["platform"], device["count"]) != (cluster.PLATFORM, 1):
        raise BenchFailure(
            f"the replica found {device}, not 1 device of "
            f"{cluster.PLATFORM!r}; nothing is run on another backend")
    return handle, t_replica, device


def run(cell: Cell, args, t_process: float, out_dir: str) -> Dict[str, Any]:
    import ray_tpu
    from ray_tpu import serve

    fam = family_of(cell.config)
    sizes = fam.sizes(cell.config)
    seed, seconds = int(args.seed), float(args.seconds)
    engine = dict(cell.settings["engine"])
    closed = cell.kind == "serve_closed"
    requests = (traffic_mod.closed_loop(cell.traffic, sizes["vocab"], seed)
                if closed else traffic_mod.open_loop(
                    cell.traffic, sizes["vocab"], seed, seconds))
    box: Dict[str, Any] = {}
    t_init = time.time()
    rt = cluster.start()
    failure = None
    try:
        handle, t_replica, device = start_replica(cell, args, fam, seed)
        t_engine = time.time()
        _warmup(handle, cell, sizes, seed)
        before = ray_tpu.get(handle.method("stats").remote(), timeout=60)
        log(f"serve: replica answered after {t_replica - t_init:.1f} s, "
            f"engine ready {t_engine - t_init:.1f} s, warm "
            f"{time.time() - t_init:.1f} s; programs "
            f"{ {k: round(v, 1) for k, v in before['programs'].items()} } "
            f"cache {before.get('compile_cache')}")
        during = _during(out_dir, box, handle, seconds, bool(args.trace))
        wall, clock = time.time(), time.perf_counter()
        if closed:
            def full() -> bool:
                return ray_tpu.get(handle.method("stats").remote(),
                                   timeout=60)["running"] \
                    >= engine["max_batch"]

            load = client.closed_loop(
                handle, requests, cell.traffic["clients"], seconds,
                sizes["vocab"], seed, during, full,
                float(cell.traffic["fill_limit_s"]))
            if load["t0"] is None:
                raise BenchFailure(
                    f"the engine's batch of {engine['max_batch']} did not "
                    f"fill within {cell.traffic['fill_limit_s']} s of the "
                    "clients' start; first request errors: " + str(
                        [r["error"] for r in load["records"]
                         if r["error"]][:3]))
        else:
            load = client.open_loop(handle, requests, seconds,
                                    cell.traffic["client_threads"], during)
        after = _settled_stats(handle)
        _await_timers(box)
        time.sleep(1.0)          # a metrics flush tick of the replica
        memory = _xla_memory()
    except BaseException as e:  # noqa: BLE001 — reported after shutdown
        failure = e
        cluster.keep_session_logs(rt.session,
                                  os.path.join(out_dir, "failure_logs"))
    finally:
        try:
            serve.shutdown()
        finally:
            left = cluster.stop(rt.session)
    if failure is not None:
        raise failure
    if left:
        raise BenchFailure(f"processes left behind: {left}")
    if "at_end" not in box or "at_start" not in box:
        raise BenchFailure("the replica gave no stats() at the window's "
                           f"edges (was it lost?): {box.get('error')}; first "
                           "request errors: " + str(
                               [r["error"] for r in load["records"]
                                if r["error"]][:3]))

    # A closed loop's clients go away at the window's end: the requests
    # they cut count neither as attempted nor as failed.
    records = [r for r in load["records"] if not r["cut"]]
    cut = len(load["records"]) - len(records)
    t0, t_end = load["t0"], load["t_end"]
    ok = [r for r in records if r["ok"]]
    problems: List[str] = []
    if after["step_errors"] or after["last_error"]:
        problems.append(f"engine step errors: {after['step_errors']}, "
                        f"last {after['last_error']}")
    if set(after["programs"]) != set(before["programs"]):
        problems.append("a program compiled inside the measured window: "
                        f"{sorted(set(after['programs']) - set(before['programs']))}")
    if after["evictions"] != before["evictions"]:
        problems.append(f"{after['evictions'] - before['evictions']} "
                        "evictions: the pool was sized so that none occurs")
    if not ok:
        problems.append("no request completed")

    tokens_in_window = sum(1 for r in load["records"] for f in r["frames"]
                           if t0 <= f <= t_end)
    ttft = [(r["frames"][0] - (r["due"] if r["due"] is not None
                               else r["sent"])) * 1e3 for r in ok]
    gaps = [(b - a) * 1e3 for r in ok
            for a, b in zip(r["frames"], r["frames"][1:])]
    late = [(r["sent"] - r["due"]) * 1e3 for r in records
            if r["due"] is not None]
    # set-up ends where the window opens (a closed loop's: batch full)
    e2e = {"setup_s": wall + (t0 - clock) - t_process}
    if closed:
        e2e["serve_tokens_per_s"] = tokens_in_window / seconds
    elif ok:
        e2e["ttft_p95_ms"] = percentile(ttft, 95)
        e2e["itl_p95_ms"] = percentile(gaps, 95)
    mid = client.in_flight_at(load["records"], t0 + seconds / 2)
    end = client.in_flight_at(load["records"], t_end)
    info = {"requests": len(records), "completed": len(ok),
            "cut_at_end": cut, "kv_pages_used_after": after["kv_pages_used"],
            "failed": len(records) - len(ok),
            "first_errors": [r["error"] for r in records
                             if not r["ok"]][:3],
            "ttft_samples": len(ttft), "itl_samples": len(gaps),
            "ttft_p50_ms": percentile(ttft, 50) if ttft else None,
            "itl_p50_ms": percentile(gaps, 50) if gaps else None,
            "tokens_in_window": tokens_in_window,
            "in_flight_mid": mid, "in_flight_end": end,
            "drain_s": load["drained"] - t_end,
            "fill_s": load.get("fill_s"),
            "late_p95_ms": percentile(late, 95) if late else None,
            "engine_steps": box["at_end"]["steps"]
            - box["at_start"]["steps"],
            "engine_tokens": box["at_end"]["tokens_generated"]
            - box["at_start"]["tokens_generated"],
            "memory": memory}
    if args.trace:
        info["capture_s"] = box["capture_s"]
        info["xplane_bytes"] = box["xplane_bytes"]
    log("serve: " + json.dumps(info))
    # every program named here ran in set-up (the warm-up runs each bucket)
    peak = max(memory["program_total"].values(), default=0.0)
    return {
        "cell": cell, "kind": cell.kind, "sizes": sizes,
        "device": device, "problems": problems,
        "attempted": len(records), "failed": len(records) - len(ok),
        "memory_peak_bytes": int(peak),
        "e2e": e2e, "info": info,
        "times": {"gang_start_s": t_replica - t_init},
        "serve": {"records": records, "t0": t0, "t_end": t_end,
                  "seconds": seconds, "before": box["at_start"],
                  "after": after, "at_end": box["at_end"],
                  "counted_s": box["t_at_end"] - box["t_at_start"],
                  "engine": engine, "ttft_ms": ttft, "gaps_ms": gaps,
                  "late_ms": late, "seed": seed},
        "trace_path": box.get("xplane"),
        "host_spans": (), "default_host": "engine_thread",
    }
