"""Distributed trace spans propagated through task submission.

Role-equivalent to the reference's OTel tracing glue (ref:
python/ray/util/tracing/tracing_helper.py:88 — the submit path injects
the current span context into the task spec; the worker opens a child
span around execution).  Dependency-free redesign: span contexts are
(trace_id, span_id) pairs riding ``TaskSpec.trace_ctx``; finished
spans are recorded as task events (the existing sink) with trace
fields, and ``trace_tree()`` reassembles the cross-process call tree.
Enable with ``RT_TRACING_ENABLED=1`` (config flag tracing_enabled).

The active context lives in a ``contextvars.ContextVar``: every thread
gets its own context (the old ``threading.local`` behavior for sync
task execution), and every asyncio task gets a *copy* of its spawner's
context — so concurrent async actor methods each adopt their own span
without cross-contaminating siblings, and nested ``.remote()`` calls
made from an async method inherit the method's span (see
core/worker_main.py _run_async_method).

Usage (driver side)::

    with tracing.start_span("ingest"):
        ref = work.remote(x)          # span context travels with it
"""

from __future__ import annotations

import contextvars
import os
import time
from typing import Any, Dict, List, Optional

_current: "contextvars.ContextVar[Optional[Dict[str, str]]]" = \
    contextvars.ContextVar("rt_span_ctx", default=None)


def _new_id(nbytes: int = 8) -> str:
    return os.urandom(nbytes).hex()


def current_span_context() -> Optional[Dict[str, str]]:
    """{"trace_id", "span_id"} of the active span, or None."""
    return _current.get()


def current_request_id() -> Optional[str]:
    """The request id of the active serve request context, or None.
    Request ids are minted at the ingress proxies (or honored from the
    client's ``X-RT-Request-Id`` header / ``rt-request-id`` gRPC
    metadata) and ride the span context through handle dispatch into
    the replica and the generation engine."""
    ctx = _current.get()
    return ctx.get("request_id") if ctx else None


def new_request_id() -> str:
    """Mint a request id (16 hex chars — short enough for headers and
    log lines, unique enough for the exemplar window)."""
    return _new_id(8)


def set_span_context(ctx: Optional[Dict[str, str]]) -> None:
    """Adopt a propagated context (the worker does this around task
    execution, so nested .remote() calls nest under the task's span).
    Scoped to the current thread or asyncio task — setting it inside
    one coroutine never leaks into a concurrently-running sibling."""
    _current.set(dict(ctx) if ctx else None)


class start_span:
    """Context manager opening a span under the current one.  On exit
    the finished span is also recorded into the process span ring
    (util/spans.py) so it shows up in the cluster timeline; while it is
    open it is a profiler annotation of the same name
    (``spans.annotate``), so a device capture shows it too."""

    def __init__(self, name: str):
        self.name = name
        self._prev: Optional[Dict[str, str]] = None
        self._annotation: Any = None
        self.ctx: Dict[str, str] = {}

    def __enter__(self) -> "start_span":
        parent = _current.get()
        self.ctx = {
            "trace_id": (parent or {}).get("trace_id") or _new_id(16),
            "span_id": _new_id(),
        }
        if parent:
            self.ctx["parent_span_id"] = parent["span_id"]
            if parent.get("request_id"):
                self.ctx["request_id"] = parent["request_id"]
        self._prev = parent
        self._t0 = time.time()
        _current.set(self.ctx)
        from . import spans as _spans

        self._annotation = _spans.annotate(self.name)
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc):
        _current.set(self._prev)
        try:
            from . import spans as _spans

            self._annotation.__exit__(None, None, None)
            _spans.record_span(self.name, self._t0, time.time(),
                               cat="span", trace=self.ctx)
        except Exception:
            pass  # the timeline must never fail user code
        return False


def inject(spec) -> None:
    """Submit-side: attach the current span context to a TaskSpec
    (ref: tracing_helper.py _inject_tracing_into_function)."""
    ctx = _current.get()
    if ctx is not None:
        spec.trace_ctx = {"trace_id": ctx["trace_id"],
                          "parent_span_id": ctx["span_id"]}
        if ctx.get("request_id"):
            spec.trace_ctx["request_id"] = ctx["request_id"]


def maybe_inject(spec, enabled: bool) -> None:
    """Inject the span context when cluster tracing is enabled OR —
    regardless of the flag — when the active context carries a serve
    request id: request-scoped tracing must follow one request through
    the replica hop without requiring cluster-wide task tracing to be
    on.  One contextvar read on the submit hot path when idle."""
    ctx = _current.get()
    if ctx is None:
        return
    if enabled or ctx.get("request_id"):
        inject(spec)


def child_context(trace_ctx: Optional[Dict[str, str]]
                  ) -> Optional[Dict[str, str]]:
    """Worker-side: the span this task executes AS."""
    if not trace_ctx:
        return None
    out = {"trace_id": trace_ctx["trace_id"],
           "span_id": _new_id(),
           "parent_span_id": trace_ctx.get("parent_span_id", "")}
    if trace_ctx.get("request_id"):
        out["request_id"] = trace_ctx["request_id"]
    return out


class request_scope:
    """Context manager establishing a serve request context: the
    request id (plus a trace id derived from it) becomes the active
    span context, so ``spans.record_span`` auto-tags every span
    recorded inside with the request id and ``maybe_inject`` carries
    it across the actor-task hop into the replica.

    Re-entrant in the nesting sense: entering with the SAME id under
    an existing scope keeps the parent linkage; entering with a new id
    starts a fresh trace.  ``rid=None`` keeps any existing context
    untouched (no-op scope) — callers without an id never pay for one.
    """

    def __init__(self, rid: Optional[str]):
        self.rid = rid
        self._prev: Optional[Dict[str, str]] = None
        self._set = False

    def __enter__(self) -> "request_scope":
        if not self.rid:
            return self
        parent = _current.get()
        ctx = {"trace_id": (parent or {}).get("trace_id")
               or f"req-{self.rid}",
               "span_id": _new_id(),
               "request_id": self.rid}
        if parent:
            ctx["parent_span_id"] = parent["span_id"]
        self._prev = parent
        self._set = True
        _current.set(ctx)
        return self

    def __exit__(self, *exc):
        if self._set:
            _current.set(self._prev)
        return False


def trace_tree(task_records: List[Dict[str, Any]],
               trace_id: Optional[str] = None) -> Dict[str, Any]:
    """Reassemble spans from the controller's task records (e.g.
    ``state.list_tasks()``): {trace_id: [span, ...]} with each span
    {span_id, parent_span_id, name, start, end, task_id}."""
    spans: Dict[str, List[Dict[str, Any]]] = {}
    for rec in task_records:
        tid = rec.get("trace_id")
        if not tid or (trace_id and tid != trace_id):
            continue
        times = list((rec.get("times") or {}).values()) or [0.0]
        spans.setdefault(tid, []).append({
            "trace_id": tid, "span_id": rec.get("span_id"),
            "parent_span_id": rec.get("parent_span_id", ""),
            "name": rec.get("name"), "task_id": rec.get("task_id"),
            "start": min(times), "end": max(times)})
    return spans
