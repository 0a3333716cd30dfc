"""Perf regression ledger — round-over-round benchmark records.

Role-equivalent to the reference's release perf harness bookkeeping
(ref: release/microbenchmark/run_microbenchmark.py writing results +
release/release_tests.yaml defining pass criteria): every recorded
benchmark run appends one JSON line per metric to ``PERF.jsonl`` at
the repo root, and ``check_regressions`` compares the latest round's
numbers against the best ever recorded — a >20% drop is a regression
the test suite fails on (tests/test_perf_ledger.py).

Record with:
  python -m ray_tpu.util.microbenchmark --record [--quick]
The ``scale/`` and ``bench/`` rows were written by two scripts that are
gone (PR 28); they stay in ``PERF.jsonl`` as data, judged by their
floors below, until ROADMAP Design 4 takes this ledger up.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Optional

DEFAULT_LEDGER = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "PERF.jsonl")

# Hard floors (ops/s, higher is better) — the round-5 VERDICT done-bars
# for the control plane plus canaries for the scale benchmarks.  A
# floored metric is judged ONLY against its floor: floors are the
# contract, while best-ever comparisons on a shared noisy CI host
# would punish one quiet run forever (the r4 ledger was recorded under
# full-suite load at ~15 ops/s; an idle run is ~50x that).
# Each entry is (floor, round the floor takes effect): records from
# earlier rounds are history, not re-judged by later bars.  The two
# batch floors are AT the round-5 VERDICT bars (3000 ops/s) effective
# r6+: the r5 rows were recorded under multi-minute noisy-neighbor
# phases on a shared CI box (tasks_batch 1883 under load vs
# 3016-3186 quiet, actor batch 2784 vs 3883-5204 quiet) before the
# floors matched the bars, and --record now stores median-of-attempts
# (the documented contract), not best-of-N.
FLOORS: Dict[str, "tuple[float, int]"] = {
    "micro/tasks_sequential": (400.0, 5),
    "micro/tasks_batch": (3000.0, 6),
    "micro/actor_calls_sequential": (400.0, 5),
    "micro/actor_calls_batch": (3000.0, 6),
    "micro/put_get_small": (300.0, 5),
    # r6 zero-stall ingest PR: the 4 MB put/get floor is 1.5x the r5
    # RECORD (436.7 ops/s) — direct local-store reads, notify-side-
    # channel registration, eager local free, and coalesced location
    # updates lift the measured rate to ~800 ops/s on the 1-core CI
    # box; 655 keeps headroom for noisy-neighbor phases while pinning
    # the improvement.
    "micro/put_get_4mb": (655.0, 6),
    "scale/many_tasks_inflight_10000": (1000.0, 5),
    "scale/queue_submit_100000": (3000.0, 5),
    # r7 control-plane fast path: warm-worker prestart pool + actor
    # adoption + batched controller registration lift actor creation
    # from 2.6 ops/s (every actor paying a full interpreter spawn) to
    # the warm-adoption regime; the floor ratchets 0.5 -> 10.0 (the
    # VERDICT "ledger floor should ratchet to the real target") with
    # headroom under the >=26 ops/s measured bar.
    "scale/many_actors_50": (10.0, 7),
    # r8 LLM inference plane: a tiny GPT-2 streamed through the
    # continuous-batching engine at saturating concurrency (8 clients).  Measured ~900-1000 tokens/s on the
    # 1-core CI box; 150 keeps the usual noisy-neighbor headroom while
    # pinning that the serving path stays an order of magnitude above
    # a sequential (batch-of-1) decode loop.  TTFT percentiles are
    # recorded unfloored (lower-is-better metrics judge against best).
    "bench/serve_llm_tokens_per_sec": (150.0, 8),
}


def record(entries: List[Dict[str, Any]], *, source: str,
           path: Optional[str] = None,
           round_tag: Optional[str] = None) -> None:
    """Append one line per metric: {ts, round, source, benchmark,
    value, unit, higher_is_better} (+ optional min/max noise bars
    when the producer ran multiple attempts)."""
    path = path or DEFAULT_LEDGER
    ts = time.time()
    tag = round_tag or os.environ.get("RT_PERF_ROUND", "")
    with open(path, "a") as f:
        for e in entries:
            row = {"ts": ts, "round": tag, "source": source,
                   "benchmark": e["benchmark"],
                   "value": float(e["value"]),
                   "unit": e.get("unit", ""),
                   "higher_is_better":
                       bool(e.get("higher_is_better", True))}
            for k in ("min", "max"):
                if k in e:
                    row[k] = float(e[k])
            f.write(json.dumps(row) + "\n")


def load(path: Optional[str] = None) -> List[Dict[str, Any]]:
    path = path or DEFAULT_LEDGER
    rows: List[Dict[str, Any]] = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if line:
                    rows.append(json.loads(line))
    except OSError:
        pass
    return rows


def check_regressions(path: Optional[str] = None, *,
                      threshold: float = 0.20,
                      source: Optional[str] = None) -> List[str]:
    """Compare each metric's LATEST record against its best earlier
    record; returns human-readable regression descriptions (empty =
    healthy).  Only metrics with >=2 records are judged — a metric's
    first record IS its baseline."""
    rows = load(path)
    if source is not None:
        rows = [r for r in rows if r["source"] == source]
    by_metric: Dict[str, List[Dict[str, Any]]] = {}
    for r in rows:
        by_metric.setdefault(
            f'{r["source"]}/{r["benchmark"]}', []).append(r)
    problems: List[str] = []
    for name, recs in by_metric.items():
        recs.sort(key=lambda r: r["ts"])
        latest = recs[-1]
        if latest.get("unit") == "share":
            # Decomposition rows (e.g. tasks_inflight_phase_*): a
            # share legitimately moves when the workload mix or an
            # optimization shifts where time goes — informational,
            # never judged against best-ever.
            continue
        floored = FLOORS.get(name)
        if floored is not None:
            floor, since_round = floored
            # Records predating a floor's effective round are history,
            # not re-judged by a later bar (r4 rows were recorded under
            # full-suite load before lease pooling existed).  Numeric
            # round parse: "r10" must still be >= since, and an
            # untagged future record is held to the floor too.
            tag = latest.get("round") or ""
            try:
                round_num = int(tag.lstrip("r") or "999")
            except ValueError:
                round_num = 999
            if round_num >= since_round and latest["value"] < floor:
                problems.append(
                    f"{name}: {latest['value']:g} is below its floor "
                    f"{floor:g} (VERDICT done-bar)")
            continue
        if len(recs) < 2:
            continue
        earlier = recs[:-1]
        hib = latest.get("higher_is_better", True)
        if hib:
            best = max(e["value"] for e in earlier)
            if best > 0 and latest["value"] < best * (1 - threshold):
                problems.append(
                    f"{name}: {latest['value']:g} is "
                    f"{100 * (1 - latest['value'] / best):.0f}% below "
                    f"best {best:g}")
        else:
            best = min(e["value"] for e in earlier)
            if best > 0 and latest["value"] > best * (1 + threshold):
                problems.append(
                    f"{name}: {latest['value']:g} is "
                    f"{100 * (latest['value'] / best - 1):.0f}% above "
                    f"best {best:g}")
    return problems
