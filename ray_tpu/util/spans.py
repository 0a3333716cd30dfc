"""Cross-process span plane — bounded per-process ring of finished spans.

PR 1's telemetry plane gave the cluster *numbers* (goodput fractions,
MFU, collective latency); this ring gives it *shape*: every process
records short-lived span records (collective ops, train-step phases,
serve requests, explicit ``tracing.start_span`` blocks) into a bounded
deque, and the existing heartbeat machinery drains them to the
controller (worker ``_flush_loop`` → node agent ``report_spans`` →
controller span sink — the same relay path flight dumps and metric
snapshots ride).  ``util/state.cluster_timeline()`` merges the sink
with the task-event records into one Chrome-trace export.

Role-equivalent to the reference's OTel span exporter behind
``ray.timeline`` + tracing_helper.py, redesigned dependency-free: a
span here is a plain dict

    {"name", "cat", "start", "end", "pid",
     "trace_id", "span_id", "parent_span_id",   # when trace-linked
     "tags": {...}}                             # e.g. op/backend/world

with wall-clock (time.time) endpoints so records from different
processes merge on one axis with the task-event sink.

Recording is always on (the ring is bounded and appends are a dict +
deque op — negligible next to any traced operation); the
``tracing_enabled`` config flag only controls trace-context
*propagation* through task submission.  This module must import
without jax or aiohttp present (tier-1 CPU guard).

``annotate`` is the one bridge from host code to the profiler's clock:
where jax is already loaded it opens a ``jax.profiler.TraceAnnotation``,
so a device capture (``rt profile --jax``, the benchmark's traced runs)
shows what the host was doing on the same axis as the device's
operations.  ``span`` and ``tracing.start_span`` enter it as well; hot
loops (the engine's step phases, the train loop) use it WITHOUT the
ring and keep a ``time.perf_counter`` sum instead.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager, nullcontext
from typing import Any, Dict, List, Optional

DEFAULT_CAPACITY = 4096


class SpanRing:
    """Thread-safe bounded ring of finished span records."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self._lock = threading.Lock()
        self._spans: "deque[Dict[str, Any]]" = deque(maxlen=capacity)
        self.total_recorded = 0

    def record(self, name: str, start: float, end: float, *,
               cat: str = "span",
               tags: Optional[Dict[str, Any]] = None,
               trace: Optional[Dict[str, str]] = None) -> None:
        """Append one finished span.  ``trace`` carries explicit
        {trace_id, span_id, parent_span_id}; when omitted, the span
        links under the caller's active tracing context (if any) so
        timeline flow arrows can connect it to its submitter."""
        ev: Dict[str, Any] = {"name": str(name), "cat": str(cat),
                              "start": float(start), "end": float(end),
                              "pid": os.getpid()}
        if trace is None:
            from . import tracing as _tracing

            cur = _tracing.current_span_context()
            if cur:
                ev["trace_id"] = cur["trace_id"]
                ev["parent_span_id"] = cur["span_id"]
                # Serve request context: every span recorded inside a
                # request_scope carries the request id, so `rt trace
                # <id>` can assemble the cross-process hop chain.
                if cur.get("request_id") and \
                        "request_id" not in (tags or {}):
                    tags = dict(tags or {})
                    tags["request_id"] = cur["request_id"]
            ev["span_id"] = _tracing._new_id()
        else:
            for k in ("trace_id", "span_id", "parent_span_id"):
                if trace.get(k):
                    ev[k] = trace[k]
        if tags:
            ev["tags"] = dict(tags)
        with self._lock:
            self._spans.append(ev)
            self.total_recorded += 1

    def drain(self) -> List[Dict[str, Any]]:
        with self._lock:
            out = list(self._spans)
            self._spans.clear()
        return out

    def snapshot(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._spans)

    def __len__(self) -> int:
        with self._lock:
            return len(self._spans)


_ring: Optional[SpanRing] = None
_ring_lock = threading.Lock()


def ring() -> SpanRing:
    """The process-global span ring (created on first use)."""
    global _ring
    if _ring is None:
        with _ring_lock:
            if _ring is None:
                _ring = SpanRing()
    return _ring


def reset() -> SpanRing:
    """Fresh global ring (tests)."""
    global _ring
    with _ring_lock:
        _ring = SpanRing()
    return _ring


def record_span(name: str, start: float, end: float, *,
                cat: str = "span",
                tags: Optional[Dict[str, Any]] = None,
                trace: Optional[Dict[str, str]] = None) -> None:
    """Append one span to the process-global ring (never raises)."""
    try:
        ring().record(name, start, end, cat=cat, tags=tags, trace=trace)
    except Exception:
        pass


_NO_ANNOTATION = nullcontext()


def annotate(name: str, **tags: Any):
    """A context manager that puts ``name`` (and ``tags``, as the event's
    stats) on the profiler's clock: a ``jax.profiler.TraceAnnotation``
    where jax is already in this process, one shared no-op otherwise.
    Never imports jax and never raises; with no capture running an
    annotation costs one atomic load.  It records nothing in the ring."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _NO_ANNOTATION
    try:
        return jax.profiler.TraceAnnotation(name, **tags)
    except Exception:   # a half-imported jax (another thread mid-import)
        return _NO_ANNOTATION


@contextmanager
def span(name: str, cat: str = "span",
         tags: Optional[Dict[str, Any]] = None):
    """Time a block and record it: ``with spans.span("load_batch"): ...``
    — unlike ``tracing.start_span`` this does not open a propagating
    trace context, it only records the timing (and shows under the same
    name in a profiler capture, see ``annotate``)."""
    t0 = time.time()
    try:
        with annotate(name):
            yield
    finally:
        record_span(name, t0, time.time(), cat=cat, tags=tags)


def drain() -> List[Dict[str, Any]]:
    return ring().drain()


def snapshot() -> List[Dict[str, Any]]:
    return ring().snapshot()


def flush(source: Optional[str] = None) -> bool:
    """Ship this process's ring straight to the controller through the
    active runtime (the driver's path — workers ride their agent flush
    loop instead).  Returns False when there is no connected runtime
    or nothing to send; never raises."""
    try:
        from ..core import runtime as runtime_mod

        rt = runtime_mod.get_runtime_quiet()
        if rt is None or not hasattr(rt, "controller_call"):
            return False
        batch = drain()
        if not batch:
            return False
        rt.controller_call("report_spans", {
            "source": source or f"driver-{os.getpid()}",
            "spans": batch})
        return True
    except Exception:
        return False
