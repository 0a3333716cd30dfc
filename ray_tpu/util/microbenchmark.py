"""Core-runtime microbenchmarks: task/actor/object throughput.

Role-equivalent to the reference's perf microbenchmark (ref:
python/ray/_private/ray_perf.py:93 + release/microbenchmark/) — the
regression canary for the control plane: schedulers, RPC, and the
object plane, independent of any ML workload.

Run: ``python -m ray_tpu.util.microbenchmark [--quick]``.
"""

from __future__ import annotations

import json
import time
from typing import Any, Callable, Dict, List


def _timeit(name: str, fn: Callable[[], int],
            results: List[Dict[str, Any]], trials: int = 3) -> None:
    """Best of N trials (ref: ray_perf.py timeit running multiple
    trials) — the sustained-rate estimate on a shared host is the
    least-interfered trial, not the mean over background noise."""
    best = 0.0
    n = 0
    dt = 0.0
    for _ in range(trials):
        t0 = time.perf_counter()
        n = fn()
        d = time.perf_counter() - t0
        if n / d > best:
            best, dt = n / d, d
    results.append({"benchmark": name, "per_sec": round(best, 1),
                    "total": n, "seconds": round(dt, 3)})


def run(quick: bool = False) -> List[Dict[str, Any]]:
    import numpy as np

    import ray_tpu

    scale = 0.2 if quick else 1.0

    @ray_tpu.remote
    def noop():
        return None

    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.n = 0

        def inc(self):
            self.n += 1
            return self.n

    results: List[Dict[str, Any]] = []

    # Steady-state warmup (ref: ray_perf.py timeit runs a warmup pass
    # before the measured trials): spawn the worker pool, populate the
    # function table, warm lease caches and code paths — cold-start
    # costs are a separate quantity from sustained throughput.
    ray_tpu.get([noop.remote() for _ in range(30)], timeout=120)
    for _ in range(20):
        ray_tpu.get(noop.remote(), timeout=60)

    n = max(int(100 * scale), 10)

    def seq_tasks():
        for _ in range(n):
            ray_tpu.get(noop.remote(), timeout=60)
        return n

    _timeit("tasks_sequential", seq_tasks, results)

    m = max(int(300 * scale), 20)

    def batch_tasks():
        ray_tpu.get([noop.remote() for _ in range(m)], timeout=120)
        return m

    ray_tpu.get([noop.remote() for _ in range(m)], timeout=120)  # warm
    _timeit("tasks_batch", batch_tasks, results)

    actor = Counter.remote()
    for _ in range(20):
        ray_tpu.get(actor.inc.remote(), timeout=60)  # warm
    ray_tpu.get([actor.inc.remote() for _ in range(50)], timeout=60)

    def seq_actor_calls():
        for _ in range(n):
            ray_tpu.get(actor.inc.remote(), timeout=60)
        return n

    _timeit("actor_calls_sequential", seq_actor_calls, results)

    def batch_actor_calls():
        ray_tpu.get([actor.inc.remote() for _ in range(m)], timeout=120)
        return m

    _timeit("actor_calls_batch", batch_actor_calls, results)

    small = {"x": 1}

    def put_get_small():
        for _ in range(n):
            ray_tpu.get(ray_tpu.put(small), timeout=60)
        return n

    _timeit("put_get_small", put_get_small, results)

    big = np.zeros((1024, 1024), np.float32)  # 4 MB
    k = max(int(20 * scale), 4)

    def put_get_4mb():
        for _ in range(k):
            ray_tpu.get(ray_tpu.put(big), timeout=60)
        return k

    _timeit("put_get_4mb", put_get_4mb, results)

    ray_tpu.kill(actor)
    return results


def main() -> None:
    import argparse

    import ray_tpu

    parser = argparse.ArgumentParser()
    parser.add_argument("--quick", action="store_true")
    parser.add_argument("--record", action="store_true",
                        help="append results to the PERF.jsonl "
                             "regression ledger")
    parser.add_argument("--attempts", type=int, default=3,
                        help="fresh-cluster attempts for --record; the "
                             "MEDIAN per metric is recorded so the "
                             "ledger reflects typical capability, not "
                             "the quietest sample (the regression "
                             "floors in perf_ledger.py are the "
                             "documented contract)")
    args = parser.parse_args()
    owns = not ray_tpu.is_initialized()
    if owns:
        ray_tpu.init(mode="cluster", num_cpus=2)
    try:
        results = run(quick=args.quick)
        attempts = {r["benchmark"]: [r] for r in results}
        if owns and args.record:
            # Fresh-cluster attempts spread over time so one
            # noisy-neighbor phase (shared CI box) can't
            # dominate every sample; the MEDIAN is what gets recorded.
            import time as _time

            for i in range(max(args.attempts - 1, 0)):
                ray_tpu.shutdown()
                _time.sleep(min(60.0 * i, 180.0))
                ray_tpu.init(mode="cluster", num_cpus=2)
                for r in run(quick=args.quick):
                    attempts[r["benchmark"]].append(r)
            import statistics

            results = []
            for name, rows in attempts.items():
                rows.sort(key=lambda r: r["per_sec"])
                rates = [r["per_sec"] for r in rows]
                median = statistics.median(rates)
                # Carry the attempt spread as noise bars: a ledger row
                # whose min..max straddles its floor is a flaky
                # signal, not a regression verdict.
                results.append({**rows[len(rows) // 2],
                                "per_sec": round(median, 1),
                                "min": round(min(rates), 1),
                                "max": round(max(rates), 1),
                                "attempts": len(rows)})
        for row in results:
            print(json.dumps(row))
    finally:
        if owns:
            ray_tpu.shutdown()
    if args.record:
        from . import perf_ledger

        source = "micro_quick" if args.quick else "micro"
        perf_ledger.record(
            [{"benchmark": r["benchmark"], "value": r["per_sec"],
              "unit": "ops/s",
              **({"min": r["min"], "max": r["max"]}
                 if "min" in r else {})}
             for r in results], source=source)


if __name__ == "__main__":
    main()
