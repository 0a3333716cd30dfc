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
ring, through ``Phases``: the one accumulator of a loop's own time, on
the wall clock and the thread's CPU clock.
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


class _Leaf:
    """One leaf of a ``Phases``: a profiler annotation and, around the
    same statements, a wall-clock pair and a CPU-clock pair added to the
    leaf's pending sums.  ``began`` and ``ended`` are the wall clock's
    two readings, for a caller that files an interval by the leaf's
    edges and reads no clock of its own (the engine's ledger of runs)."""

    __slots__ = ("_phases", "_sums", "_annotation", "_t0", "began", "ended")

    def __init__(self, phases: "Phases", name: str, annotation):
        self._phases, self._sums = phases, phases._pending[name]
        self._annotation = annotation

    def __enter__(self) -> "_Leaf":
        self._annotation.__enter__()
        self._t0 = self._phases.clocks()
        self.began = self._t0[0]
        return self

    def __exit__(self, *exc) -> bool:
        wall, cpu = self._phases.clocks()
        self.ended = wall
        self._sums[0] += wall - self._t0[0]
        self._sums[1] += cpu - self._t0[1]
        self._annotation.__exit__(*exc)
        return False


class Phases:
    """A loop's account of its own time: leaves + ``other`` = whole, on
    the wall clock and on the loop thread's CPU clock (wall less CPU is
    time the thread waited: for the device, a lock, the GIL).

    A whole is one turn of the loop (an engine step; a trainer's period
    from the end of one report to the end of the next), with its own
    sums and a count.  The loop's thread keeps a whole's leaves pending
    and hands them over with the whole's own time in one go, under
    ``lock`` (the owner's, so that its ``stats()`` reads them with its
    other counters: call ``totals`` with the lock held), so a reading
    taken mid-whole still adds up.  ``apart`` times what runs on another
    thread, outside that identity.  Nothing goes into the span ring.

    The CPU clock is a system call (6 us on the chip's host, where the
    wall clock takes 0.13: 26 reads a step were 2% of an 8.9 ms engine
    step), so it is read on one whole in ``cpu_every`` only: the CPU
    sums are over those wholes, and ``cpu_sample`` holds the wall
    clock's sums over the SAME wholes to hold them against."""

    def __init__(self, leaves, other: str, lock=None, cpu_every: int = 1,
                 clock=time.perf_counter, cpu_clock=time.thread_time):
        self.other = other
        self.lock = lock if lock is not None else threading.Lock()
        self._clock, self._cpu_clock = clock, cpu_clock
        self._cpu_every = cpu_every
        self._pending = {name: [0.0, 0.0] for name in leaves}  # wall, CPU
        # wall, CPU, and the wall of the wholes whose CPU time was read
        self._sums = {name: [0.0, 0.0, 0.0] for name in leaves}
        self._whole = [0.0, 0.0, 0.0]
        self._t0 = None             # the open whole's start, both clocks
        self._begun = 0
        self._cpu_on = self._void = False
        self.count = 0              # wholes handed over
        self.cpu_count = 0          # those whose CPU time was read
        self.longest_s = 0.0        # the longest of them, wall
        self.apart_s: Dict[str, List[float]] = {}   # name -> [seconds, n]

    def clocks(self):
        """(wall, CPU) now; CPU reads 0.0 throughout a whole that does
        not sample it, so differences inside one whole stay right."""
        return self._clock(), self._cpu_clock() if self._cpu_on else 0.0

    def leaf(self, name: str, **tags: Any) -> _Leaf:
        return _Leaf(self, name, annotate(name, **tags))

    def add(self, name: str, wall_s: float, cpu_s: float) -> None:
        """A leaf the caller timed itself (its self time, say)."""
        sums = self._pending[name]
        sums[0] += wall_s
        sums[1] += cpu_s

    def void(self) -> None:
        """The open whole holds something that is no part of a turn (a
        compile): it will be dropped, leaves and all."""
        self._void = True

    def _begin(self, wall: Optional[float] = None) -> None:
        self._cpu_on = self._begun % self._cpu_every == 0
        self._begun += 1
        self._void = False
        self._t0 = (self._clock() if wall is None else wall,
                    self._cpu_clock() if self._cpu_on else 0.0)

    def _end(self, now) -> None:
        """Hand the open whole over, if there is one and it is kept."""
        with self.lock:
            keep = self._t0 is not None and not self._void
            for name, pending in self._pending.items():
                if keep:
                    sums = self._sums[name]
                    sums[0] += pending[0]
                    if self._cpu_on:
                        sums[1] += pending[1]
                        sums[2] += pending[0]
                pending[0] = pending[1] = 0.0
            if keep:
                wall = now[0] - self._t0[0]
                self._whole[0] += wall
                self.count += 1
                self.longest_s = max(self.longest_s, wall)
                if self._cpu_on:
                    self._whole[1] += now[1] - self._t0[1]
                    self._whole[2] += wall
                    self.cpu_count += 1
        self._t0 = None

    def close(self) -> None:
        """End the open whole, if there is one, and start the next at
        the same instant (a loop whose wholes follow one another)."""
        now = self.clocks()
        self._end(now)
        self._begin(now[0])

    @contextmanager
    def whole(self):
        """One whole around a block: what lies between two is nobody's."""
        self._begin()
        try:
            yield
        finally:
            self._end(self.clocks())

    @contextmanager
    def apart(self, name: str, **tags: Any):
        """Time a block of ANOTHER thread under its own sum and count."""
        t0 = self._clock()
        try:
            with annotate(name, **tags):
                yield
        finally:
            dt = self._clock() - t0
            with self.lock:
                acc = self.apart_s.setdefault(name, [0.0, 0])
                acc[0] += dt
                acc[1] += 1

    def totals(self) -> Dict[str, Any]:
        """Cumulative seconds; the leaves and ``other`` sum to
        ``step_s``.  ``phase_cpu_s`` and ``step_cpu_s`` are over the
        ``cpu_sample["steps"]`` wholes whose CPU time was read, and
        ``cpu_sample`` the wall clock's ``phase_s`` / ``step_s`` over
        those same wholes.  Call with ``lock`` held."""
        phase_s, cpu_s, sample_s = (
            {name: s[i] for name, s in self._sums.items()} for i in range(3))
        return {"phase_s": phase_s, "phase_cpu_s": cpu_s,
                "step_s": self._whole[0], "step_cpu_s": self._whole[1],
                self.other: self._whole[0] - sum(phase_s.values()),
                "cpu_sample": {"steps": self.cpu_count, "phase_s": sample_s,
                               "step_s": self._whole[2]}}


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
