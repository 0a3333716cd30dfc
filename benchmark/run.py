#!/usr/bin/env python3
"""One run of one cell:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell's files by the names in BENCHMARK.json (benchmark/README.md),
starts the cluster, warms up the cell's shapes (set-up), measures for
--seconds, checks the outputs against the plain reference, and prints as
the LAST line of stdout one JSON object: correct, attempted, failed,
metrics, device (and breakdown with --trace 1).  With --trace 0 the metrics
are the cell's end-to-end metrics, with --trace 1 its per-layer metrics.

This process never starts a JAX backend: the worker or the replica holds
the chip.  Where the host shows fewer chips than the cell asks for it exits
non-zero and prints no result; nothing is measured on the CPU instead.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse          # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402
import traceback         # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:] = [ROOT] + [p for p in sys.path if p != ROOT]   # first


def emit(obj) -> None:
    print(json.dumps(obj), flush=True)


def _check(ctx, cell, out_dir: str, log) -> None:
    """``correct``, the reference's part: appends to ctx["problems"]."""
    from benchmark.harness import check

    spec = {"config": cell.config, "seed": ctx["seed"]}
    if ctx["kind"] == "train":
        spec.update(kind="train", traffic=cell.traffic, sizes=ctx["sizes"],
                    check_file=ctx["train"]["check_file"])
        ctx["check"] = check.judge_train(
            ctx["train"]["check_step"], check.run_child(spec, out_dir),
            cell.traffic["check"])
        ctx["problems"].extend(ctx["check"].pop("problems"))
    else:
        import numpy as np

        c = cell.traffic["check"]
        fits = [r for r in ctx["serve"]["records"]
                if r["ok"] and r["greedy"]
                and r["prompt_len"] + r["max_tokens"] <= c["max_positions"]]
        fits.sort(key=lambda r: (r["index"], r["sent"]))
        rng = np.random.default_rng([ctx["seed"], 0x636b])
        picks = [fits[i] for i in sorted(rng.choice(
            len(fits), size=min(c["greedy_sample"], len(fits)),
            replace=False))] if fits else []
        if not picks:
            ctx["problems"].append("no completed greedy request of at most "
                                   f"{c['max_positions']} positions to "
                                   "compare with the reference")
            return
        spec.update(kind="serve", samples=[
            {"index": r["index"], "tokens": r["tokens"],
             "prompt": r["prompt"]} for r in picks])
        ref = check.run_child(spec, out_dir)
        ctx["check"] = dict(ref, tolerance=c["logit_tolerance"])
        if not ref["max_logit_gap"] <= c["logit_tolerance"]:
            ctx["problems"].append(
                f"a served token's reference logit lies "
                f"{ref['max_logit_gap']} under the largest "
                f"(> {c['logit_tolerance']})")
    log("check: " + json.dumps(ctx["check"]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        from benchmark.harness import (cluster, manifest, peaks,
                                       serve_runner, trace, train_runner)
        import ray_tpu  # noqa: F401 — the system under test
    except ImportError as e:
        print(f"benchmark: cannot import the system under test: {e!r}",
              file=sys.stderr)
        return 2
    log = cluster.log
    try:
        cell = manifest.load_cell(args.workload, ROOT)
        readers = {m.name: manifest.load_reader(m.name, ROOT)
                   for m in cell.per_layer} if args.trace else {}
        run_seconds = manifest.load_manifest(ROOT)["run_seconds"]
    except manifest.ManifestError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    if args.seconds is None:
        args.seconds = float(run_seconds)

    cluster.prepare_environment()
    have = cluster.chips_on_host()
    if have < cell.chips:
        print(f"benchmark: found no accelerator to run on: this host shows "
              f"{have} TPU chip(s), cell {cell.name!r} needs {cell.chips}; "
              "nothing is measured on the CPU in its place",
              file=sys.stderr)
        return 3

    out_dir = os.path.join(cluster.OUT_ROOT, cell.name)
    shutil.rmtree(os.path.join(out_dir, "trace"), ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)
    runner = train_runner if cell.kind == "train" else serve_runner
    try:
        ctx = runner.run(cell, args, T_PROCESS, out_dir)
        ctx["seed"] = int(args.seed)
        device = ctx["device"]
        ctx["peaks"] = peaks.peaks_for(device["kind"])
        ctx["trace"] = None
        if args.trace:
            if not ctx.get("trace_path"):
                # a training run's: serve_runner raises its own three
                raise cluster.BenchFailure(
                    "the traced run left " + serve_runner.no_xplane_under(
                        os.path.join(out_dir, "trace")))
            ctx["trace"] = trace.reduce(
                trace.load(ctx["trace_path"], ctx["host_spans"]),
                ctx["host_spans"], ctx["default_host"])
            if not ctx["trace"]["busy_s"] > 0:
                raise cluster.BenchFailure(
                    "no operation ran on the device in the traced window")
        _check(ctx, cell, out_dir, log)
    except BaseException as e:  # noqa: BLE001 — no result line
        traceback.print_exception(e, file=sys.stderr)
        print(f"benchmark: FAILED: {e!r}", file=sys.stderr)
        return 1

    metrics = {}
    if args.trace:
        for m in cell.per_layer:
            value = readers[m.name](ctx)
            if value is not None:
                metrics[m.name] = {"value": float(value), "unit": m.unit}
    else:
        for m in cell.end_to_end:
            if m.name in ctx["e2e"]:
                metrics[m.name] = {"value": float(ctx["e2e"][m.name]),
                                   "unit": m.unit}
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"],
           "memory_peak_bytes": ctx["memory_peak_bytes"]}
    line = {"correct": not ctx["problems"], "attempted": ctx["attempted"],
            "failed": ctx["failed"], "metrics": metrics, "device": dev}
    if args.trace:
        t = ctx["trace"]
        dev["busy_s"], dev["window_s"] = t["busy_s"], t["window_s"]
        line["breakdown"] = {"device_ops": t["device_ops"],
                             "idle_gaps": t["idle_gaps"]}
    # Earlier lines: what a reader of the run wants beside the result.
    emit({"info": {"cell": cell.name, "seed": args.seed,
                   "seconds": args.seconds, "trace": args.trace,
                   "end_to_end": ctx["e2e"], "times": ctx["times"],
                   "check": ctx.get("check"), "detail": ctx.get("info"),
                   "problems": ctx["problems"],
                   "run_s": time.time() - T_PROCESS}})
    emit(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
