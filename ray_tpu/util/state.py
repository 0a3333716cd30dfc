"""Cluster state API: list/get tasks, actors, objects, nodes, jobs,
placement groups — plus Chrome-trace timeline export.

Role-equivalent to the reference's ray.util.state (ref:
python/ray/util/state/api.py backed by GCS task events,
gcs_task_manager.h:86) and ray.timeline (ref: _private/state.py:960).
Works from a connected driver (uses the runtime's controller channel) or
standalone by address (``rt list ...`` CLI path).
"""

from __future__ import annotations

import asyncio
import json
from typing import Any, Dict, List, Optional


def _call(method: str, payload: Optional[Dict] = None,
          address: Optional[str] = None) -> Any:
    from ..core import runtime as runtime_mod

    rt = runtime_mod.get_runtime_quiet()
    if rt is not None and hasattr(rt, "controller_call") and address is None:
        return rt.controller_call(method, payload or {})
    from ..core.rpc import RpcClient
    from ..scripts.cli import resolve_address

    addr = resolve_address(address=address)
    if addr is None:
        raise ConnectionError(
            "No cluster: call ray_tpu.init() first or pass address=.")

    async def _go():
        cli = RpcClient(addr, connect_timeout=10.0)
        try:
            return await cli.call(method, payload or {})
        finally:
            await cli.close()

    return asyncio.run(_go())


def list_tasks(*, state: Optional[str] = None, name: Optional[str] = None,
               limit: int = 1000,
               address: Optional[str] = None) -> List[Dict]:
    """Task records from the controller sink.  ``state`` filters on
    RUNNING / FINISHED / FAILED."""
    r = _call("list_tasks", {"state": state, "name": name, "limit": limit},
              address)
    return r["tasks"]


def get_task(task_id: str, *, address: Optional[str] = None
             ) -> Optional[Dict]:
    return _call("get_task", {"task_id": task_id}, address)


def list_actors(*, address: Optional[str] = None) -> List[Dict]:
    actors = _call("list_actors", {}, address)
    out = []
    for a in actors:
        d = dict(a)
        for k in ("actor_id", "node_id"):
            v = d.get(k)
            if hasattr(v, "hex"):
                d[k] = v.hex()
        out.append(d)
    return out


def list_nodes(*, address: Optional[str] = None) -> List[Dict]:
    nodes = _call("list_nodes", {}, address)
    out = []
    for n in nodes:
        d = dict(n)
        v = d.get("node_id")
        if hasattr(v, "hex"):
            d["node_id"] = v.hex()
        out.append(d)
    return out


def list_objects(*, limit: int = 1000,
                 address: Optional[str] = None) -> List[Dict]:
    return _call("list_objects", {"limit": limit}, address)["objects"]


def list_jobs(*, address: Optional[str] = None) -> List[Dict]:
    return _call("list_jobs", {}, address)["jobs"]


def jobs_overview(job_id: Optional[str] = None, *,
                  address: Optional[str] = None) -> List[Dict]:
    """The multi-tenant job plane (`rt jobs` / /api/jobs): every
    submitted job with priority, quota, live resource usage, state,
    submission time, and any active preemption notice.  ``job_id``
    prefix-filters (the `rt explain` convention)."""
    return _call("jobs_overview", {"job_id": job_id or ""},
                 address)["jobs"]


def preempt_job(job_id: str, *, reason: str = "operator preemption",
                grace_s: Optional[float] = None,
                address: Optional[str] = None) -> Dict[str, Any]:
    """Mark a job for preemption (checkpoint-on-notice, then gang
    eviction at the grace deadline) — the operator-driven path the
    scheduler's automatic victim selection also uses."""
    payload: Dict[str, Any] = {"job_id": job_id, "reason": reason}
    if grace_s is not None:
        payload["grace_s"] = grace_s
    return _call("preempt_job", payload, address)


def list_placement_groups(*, address: Optional[str] = None) -> List[Dict]:
    pgs = _call("list_placement_groups", {}, address)
    return [dict(p) for p in pgs] if isinstance(pgs, list) else pgs


def metrics_text(*, address: Optional[str] = None) -> str:
    """Cluster-wide Prometheus exposition text."""
    return _call("metrics_text", {}, address)["text"]


def metrics_history(*, source: Optional[str] = None,
                    address: Optional[str] = None) -> Dict[str, Any]:
    """Per-node metric time series: {source: [[ts, {metric: value}],
    ...]} over the controller's retained window (ref:
    dashboard/modules/reporter/ utilization history)."""
    return _call("metrics_history", {"source": source}, address)


def hotpath(*, address: Optional[str] = None) -> Dict[str, Any]:
    """Cluster-wide hot-path phase decomposition: sampled task
    lifecycle stamps sliced into named phases (submit -> lease ->
    transit -> exec -> reply) with per-phase p50/p99 and mean shares.
    Rendered by `rt hotpath`; see ``ray_tpu.util.hotpath``."""
    return _call("hotpath", {}, address)


def telemetry(*, address: Optional[str] = None) -> Dict[str, Any]:
    """Raw training-telemetry feed: latest per-source metric snapshots
    + retained flight-recorder dumps.  Use
    ``ray_tpu.util.telemetry.cluster_summary`` for the aggregated
    operator view (`rt telemetry`)."""
    return _call("telemetry", {}, address)


def timeline(filename: Optional[str] = None, *,
             address: Optional[str] = None) -> Any:
    """Chrome-trace (chrome://tracing / perfetto) export of task events
    (ref: ray.timeline, _private/state.py:960).  Task-only, driver-local
    view; ``cluster_timeline`` is the merged cluster-wide export.

    Still-RUNNING tasks export as an ``X`` clipped to now with
    ``args.state == "RUNNING"`` — an unmatched ``B`` renders as an
    unclosed/zero-length slice in Perfetto.

    Returns the trace list; writes JSON to ``filename`` if given.
    """
    import time as _time

    from .timeline import build_trace

    tasks = list_tasks(limit=100000, address=address)
    trace = build_trace(tasks, now=_time.time())
    if filename:
        with open(filename, "w") as f:
            json.dump(trace, f)
    return trace


def list_spans(*, limit: int = 10000, cat: Optional[str] = None,
               address: Optional[str] = None) -> List[Dict]:
    """Span records from the controller's cross-process span sink
    (collectives, goodput phases, train steps, serve requests,
    explicit tracing spans)."""
    r = _call("list_spans", {"limit": limit, "cat": cat}, address)
    return r["spans"]


def cluster_timeline(filename: Optional[str] = None, *,
                     address: Optional[str] = None) -> List[Dict]:
    """The unified cluster timeline: task events + the cross-process
    span plane + MFU/goodput/serve counter tracks merged into ONE
    Chrome-trace export — one ``pid`` track per node, ``tid`` per
    worker, flow arrows linking submitter spans to their remote
    executions (ref: ray.timeline + OTel span injection, redesigned
    over the controller span sink).

    Returns the trace list; writes JSON to ``filename`` if given.
    """
    import time as _time

    from . import spans as spans_mod
    from .timeline import build_trace

    # Ship this process's own ring first so driver-side spans make the
    # export (workers ride their agent flush loop; the driver has none).
    spans_mod.flush()
    tasks = list_tasks(limit=100000, address=address)
    spans = list_spans(limit=100000, address=address)
    try:
        history = metrics_history(address=address)
    except Exception:
        history = {}
    trace = build_trace(tasks, spans, history, now=_time.time())
    if filename:
        with open(filename, "w") as f:
            json.dump(trace, f)
    return trace


def timeline_summary(*, address: Optional[str] = None) -> Dict[str, Any]:
    """Per-step critical path from the span sink: slowest rank per
    training step + the goodput phase that dominated its wait (the
    ``rt timeline --summary`` data)."""
    from .timeline import critical_path_summary

    return critical_path_summary(list_spans(limit=100000,
                                            address=address))


def request_exemplars(*, address: Optional[str] = None
                      ) -> Dict[str, Any]:
    """The controller's slowest-request exemplar ring (slowest-first,
    bounded per window): {"exemplars": [{request_id, duration_s,
    deployment, ts, ...}], "window_s"} — the `rt trace` listing and
    the doctor's find_slow_requests input."""
    return _call("request_exemplars", {}, address)


def request_trace(request_id: str, *, address: Optional[str] = None
                  ) -> Dict[str, Any]:
    """Assemble one request's cross-process hop chain (proxy ->
    admission -> attempt -> replica -> engine) from the span sink —
    the `rt trace <request_id>` data.  ``request_id`` may be a prefix;
    ambiguity is reported rather than guessed."""
    from . import spans as spans_mod
    from .reqtrace import assemble_trace, find_request_ids

    # Ship this process's own ring first (driver-side spans).
    spans_mod.flush()
    spans = list_spans(limit=100000, address=address)
    ids = find_request_ids(spans, prefix=request_id)
    if len(ids) > 1 and request_id not in ids:
        return {"request_id": request_id, "found": False,
                "ambiguous": ids[:10]}
    rid = request_id if request_id in ids else (ids[0] if ids
                                                else request_id)
    return assemble_trace(spans, rid)


def explain_task(task_id: str, *, address: Optional[str] = None
                 ) -> Dict[str, Any]:
    """Scheduler explainability: the full transition chain (queued ->
    lease_requested -> pipelined/granted -> running -> finished/
    requeued, each with reason tags) of one task — ``rt explain``.
    Accepts a task-id prefix."""
    return _call("explain_task", {"task_id": task_id}, address)


def doctor_feed(*, address: Optional[str] = None) -> Dict[str, Any]:
    """Raw controller health feed: merged collective-entry stamps,
    the autoscaler decision ring, retained flight dumps."""
    return _call("doctor_feed", {}, address)


def load_metrics(*, address: Optional[str] = None) -> Dict[str, Any]:
    """The autoscaler's input view: per-node utilization/idle age +
    the cluster demand vector."""
    return _call("get_load_metrics", {}, address)


def serve_resilience(*, address: Optional[str] = None
                     ) -> Dict[str, Any]:
    """The serve resilience plane's published stats (replica
    replacement log, reported breaker states, admission-queue depth
    per deployment), mirrored by the serve controller into the
    cluster KV so `rt doctor` / `rt telemetry` read it over the plain
    controller RPC.  Empty dict when serve is not running."""
    import json as _json

    try:
        raw = _call("kv_get", {"key": "serve/resilience"}, address)
    except Exception:
        return {}
    if not raw:
        return {}
    try:
        if isinstance(raw, (bytes, bytearray)):
            raw = raw.decode()
        return _json.loads(raw)
    except Exception:
        return {}


def list_leases(*, node_id: Optional[str] = None,
                address: Optional[str] = None) -> List[Dict]:
    """Fan out over alive node agents and return each node's lease
    ledger (held leases with owner tag / pipeline depth / idle age,
    queued lease requests, and the advertised demand vector) — the
    ``rt list leases`` data."""
    out = []
    for n in _agents(node_id, address):
        try:
            out.append(_agent_call(n["agent_addr"], "list_leases"))
        except Exception as e:  # noqa: BLE001 — one dead agent must
            # not hide every other node's ledger
            out.append({"node_id": n["node_id"],
                        "error": f"agent unreachable: {e}"})
    return out


def worker_pools(*, node_id: Optional[str] = None,
                 address: Optional[str] = None) -> List[Dict]:
    """Fan out over alive node agents and return each node's warm
    prestart-pool books (occupancy, adoption vs cold-spawn counters,
    startup-phase sample counts) — the scale benches' pool-hit report
    and the data behind the `rt status` pool column."""
    out = []
    for n in _agents(node_id, address):
        try:
            out.append(_agent_call(n["agent_addr"], "pool_stats"))
        except Exception as e:  # noqa: BLE001 — one dead agent must
            # not hide every other node's pool
            out.append({"node_id": n["node_id"],
                        "error": f"agent unreachable: {e}"})
    return out


def doctor(*, address: Optional[str] = None) -> Dict[str, Any]:
    """The aggregated health diagnosis (``rt doctor`` /
    ``/api/doctor``); see util/doctor.py for the checks."""
    from . import doctor as doctor_mod

    return doctor_mod.cluster_diagnosis(address=address)


def perf(*, address: Optional[str] = None) -> Dict[str, Any]:
    """The XLA performance introspection report (``rt perf`` /
    ``/api/perf``): roofline position, step decomposition, per-axis
    collective shares, compile events, device-memory watermarks; see
    util/xprof.py."""
    from . import xprof as xprof_mod

    return xprof_mod.cluster_report(address=address)


def summarize_tasks(*, address: Optional[str] = None) -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for rec in list_tasks(limit=100000, address=address):
        s = rec.get("state", "?")
        counts[s] = counts.get(s, 0) + 1
    return counts


# ---------------------------------------------------------------- log plane
def _agent_call(agent_addr: str, method: str,
                payload: Optional[Dict] = None) -> Any:
    from ..core.rpc import RpcClient

    async def _go():
        cli = RpcClient(agent_addr, connect_timeout=10.0)
        try:
            return await cli.call(method, payload or {})
        finally:
            await cli.close()

    return asyncio.run(_go())


def _agents(node_id: Optional[str], address: Optional[str]) -> List[Dict]:
    nodes = [n for n in list_nodes(address=address) if n["alive"]]
    if node_id:
        nodes = [n for n in nodes
                 if str(n["node_id"]).startswith(node_id)]
    return nodes


def list_logs(*, node_id: Optional[str] = None,
              address: Optional[str] = None) -> List[Dict]:
    """Per-worker log-file inventory across nodes (ref:
    dashboard/modules/log/ listing)."""
    out = []
    for n in _agents(node_id, address):
        r = _agent_call(n["agent_addr"], "list_worker_logs")
        for rec in r["logs"]:
            out.append({"node_id": n["node_id"], **rec})
    return out


def get_log(*, worker_id: Optional[str] = None,
            pid: Optional[int] = None,
            node_id: Optional[str] = None,
            max_bytes: int = 256 * 1024,
            address: Optional[str] = None) -> str:
    """Fetch a worker's stdout/stderr tail — dead workers included
    (ref: `ray logs`, dashboard/modules/log/)."""
    req: Dict[str, Any] = {"max_bytes": max_bytes}
    if worker_id:
        req["worker_id"] = worker_id
    if pid is not None:
        req["pid"] = pid
    for n in _agents(node_id, address):
        r = _agent_call(n["agent_addr"], "read_worker_log", req)
        if r.get("ok"):
            return r["text"]
    raise ValueError("worker log not found on any alive node")


def profile_worker(*, worker_id: Optional[str] = None,
                   pid: Optional[int] = None,
                   node_id: Optional[str] = None,
                   duration_s: float = 2.0, hz: float = 100.0,
                   address: Optional[str] = None) -> Dict[str, int]:
    """Sampling-profile a live worker; returns folded stacks (ref:
    profile_manager.py:121 — see util/profiling.py for the in-process
    redesign)."""
    req: Dict[str, Any] = {"duration_s": duration_s, "hz": hz}
    if worker_id:
        req["worker_id"] = worker_id
    if pid is not None:
        req["pid"] = pid
    for n in _agents(node_id, address):
        r = _agent_call(n["agent_addr"], "profile_worker", req)
        if r.get("ok"):
            return r["folded"]
    raise ValueError("worker not found on any alive node")


def jax_profile(*, duration_s: float = 3.0,
                node_id: Optional[str] = None,
                force: bool = False,
                python_tracer: bool = False,
                address: Optional[str] = None) -> List[Dict]:
    """Start an on-demand ``jax.profiler`` capture on every live worker
    (optionally filtered by node prefix) and return
    [{node_id, pid, ok, path|error}, ...].  Workers that never imported
    jax are skipped unless ``force`` (the tier-1 CPU guard); artifact
    paths are also reported to the controller (``telemetry()`` →
    ``profiles``).

    A capture holds the device's programs and operations (kernels and
    ``jax.named_scope`` names) and the host's ``spans.annotate``
    annotations (``llm.*``, ``train.*``, span blocks) on one clock.
    ``python_tracer`` also traces every Python call: it slows the host
    loop being measured and makes the trace many times larger, so it
    is off by default."""
    from concurrent.futures import ThreadPoolExecutor

    nodes = _agents(node_id, address)
    if not nodes:
        return []

    def _one(n):
        try:
            r = _agent_call(n["agent_addr"], "jax_profile_workers",
                            {"duration_s": duration_s, "force": force,
                             "python_tracer": python_tracer})
        except Exception as e:  # noqa: BLE001 — one dead agent must
            # not discard every other node's finished capture
            return [{"node_id": n["node_id"], "pid": -1, "ok": False,
                     "error": f"agent unreachable: {e}"}]
        return [{"node_id": n["node_id"], **rec}
                for rec in r.get("results", [])]

    # Concurrent fan-out: every node captures the SAME wall-clock
    # window, so one distributed train step shows up on all ranks
    # (sequential capture would record disjoint windows).
    out: List[Dict] = []
    with ThreadPoolExecutor(max_workers=min(len(nodes), 16)) as ex:
        for rows in ex.map(_one, nodes):
            out.extend(rows)
    return out


def stack_worker(*, worker_id: Optional[str] = None,
                 pid: Optional[int] = None,
                 node_id: Optional[str] = None,
                 address: Optional[str] = None) -> str:
    """All-thread stack dump of a live worker (py-spy --dump role)."""
    req: Dict[str, Any] = {}
    if worker_id:
        req["worker_id"] = worker_id
    if pid is not None:
        req["pid"] = pid
    for n in _agents(node_id, address):
        r = _agent_call(n["agent_addr"], "stack_worker", req)
        if r.get("ok"):
            return r["stacks"]
    raise ValueError("worker not found on any alive node")
