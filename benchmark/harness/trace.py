"""From a profiler trace (``.xplane.pb``) to numbers: device busy and idle
time, per-program time, kernel time, exposed collective time, the device
operations that took most time and the longest idle gaps by what the host
was doing.  Read with ``jax.profiler.ProfileData``; nothing else.

What a TPU trace looks like today (read by hand off a v5e, PR 23): one
plane per chip named ``/device:TPU:<n>`` with the lines ``XLA Modules``
(one event per run of a jitted program, named ``jit_<fn>(<fingerprint>)``)
and ``XLA Ops`` (one event per HLO instruction run, named by the
instruction's whole text, ``%name = shape opcode(operands), attributes``;
a ``while`` holds its body's events nested inside it).  A Pallas kernel is
a ``custom-call`` whose text says ``custom_call_target="tpu_custom_call"``.
Host threads are lines of the plane ``/host:CPU``; a
``jax.profiler.TraceAnnotation`` is an event there under its own name.  All
times are nanoseconds from one origin; host and device clocks agree to a
few tenths of a millisecond.  Stable names are the `tracing` issue's.
"""

from __future__ import annotations

import re
from collections import Counter, defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPCODE = re.compile(r" ([a-z][a-z\-]*)\(")
PARENTS = ("while", "conditional", "call")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
WINDOW_ANNOTATION = "bench_window"


@dataclass
class DevicePlane:
    name: str
    modules: List[Tuple[str, float, float]] = field(default_factory=list)
    ops: List[Tuple[str, float, float]] = field(default_factory=list)


@dataclass
class Trace:
    devices: List[DevicePlane]
    host: List[Tuple[str, float, float]]    # named host events


def load(path: str, host_names: Sequence[str] = ()) -> Trace:
    """Device planes' module and op events, and the host events whose name
    is in ``host_names`` (or is the window annotation)."""
    from jax.profiler import ProfileData

    keep = set(host_names) | {WINDOW_ANNOTATION}
    devices, host = [], []
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            dev = DevicePlane(plane.name)
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev.modules = [(e.name, e.start_ns, e.duration_ns)
                                   for e in line.events]
                elif line.name == "XLA Ops":
                    dev.ops = [(e.name, e.start_ns, e.duration_ns)
                               for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events if e.name in keep)
    devices.sort(key=lambda d: d.name)
    return Trace(devices, host)


# ------------------------------------------------------------ intervals

def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The parts of the merged intervals ``a`` that no interval of the
    merged ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


# ------------------------------------------------------------ op names

def opcode(op_name: str) -> str:
    m = OPCODE.search(op_name)
    return m.group(1) if m else ""


def is_kernel(op_name: str) -> bool:
    return 'custom_call_target="tpu_custom_call"' in op_name


def is_collective(op_name: str) -> bool:
    code = opcode(op_name)
    return any(code == c or code == c + "-start" or code == c + "-done"
               for c in COLLECTIVES)


def op_label(op_name: str) -> str:
    """A short name an operation is grouped under: the instruction's name
    with its numbers taken out, its opcode, and a fusion's kind or a
    custom call's target."""
    base = re.sub(r"\.\d+$", "", op_name.split(" ", 1)[0].lstrip("%"))
    base = re.sub(r"\d+", "N", base)
    code = opcode(op_name)
    if base == code:
        base = ""
    extra = ""
    m = re.search(r"kind=(k\w+)", op_name) if code == "fusion" else \
        re.search(r'custom_call_target="([^"]+)"', op_name)
    if m:
        extra = " " + m.group(1)
    return f"{base} {code}{extra}".strip()


# ------------------------------------------------------------ reduction

def window_of(trace: Trace) -> Interval:
    """The traced window: the ``bench_window`` annotation where the traced
    code made one, else from the first to the last device operation."""
    marks = [(s, s + d) for n, s, d in trace.host if n == WINDOW_ANNOTATION]
    if marks:
        return max(marks, key=lambda i: i[1] - i[0])
    spans = [(s, s + d) for dev in trace.devices for _, s, d in dev.ops]
    if not spans:
        raise ValueError("the trace holds no device operation")
    return min(s for s, _ in spans), max(e for _, e in spans)


def _leaves(dev: DevicePlane, lo: float, hi: float):
    for name, s, d in dev.ops:
        if d > 0 and s < hi and s + d > lo \
                and opcode(name) not in PARENTS:
            yield name, max(s, lo), min(s + d, hi)


def reduce(trace: Trace, host_names: Sequence[str] = (),
           default_host: str = "host") -> Dict[str, object]:
    """Everything the per-layer readers and the result line take from a
    trace.  Seconds throughout; per-device quantities are averaged over
    the device planes."""
    lo, hi = window_of(trace)
    n_dev = len(trace.devices)
    if not n_dev:
        raise ValueError("the trace holds no device plane")
    busy_s = kernel_s = exposed_s = collective_s = 0.0
    op_time: Dict[str, float] = defaultdict(float)
    programs: Dict[str, List[float]] = defaultdict(list)
    gaps_by: Dict[str, float] = defaultdict(float)
    named = set(host_names)
    host_spans = sorted((s, s + d, n) for n, s, d in trace.host
                        if n in named)
    for dev in trace.devices:
        busy = union(clip(((s, s + d) for _, s, d in dev.ops), lo, hi))
        busy_s += total(busy) / 1e9
        coll, compute = [], []
        for name, s, e in _leaves(dev, lo, hi):
            op_time[op_label(name)] += (e - s) / 1e9 / n_dev
            if is_kernel(name):
                kernel_s += (e - s) / 1e9
            (coll if is_collective(name) else compute).append((s, e))
        coll_u, comp_u = union(coll), union(compute)
        collective_s += total(coll_u) / 1e9
        exposed_s += total(subtract(coll_u, comp_u)) / 1e9
        for name, s, d in dev.modules:
            if lo <= s + d / 2 <= hi:
                programs[name].append(d / 1e9)
        for gs, ge in subtract([(lo, hi)], busy):
            gaps_by[_host_label(gs, ge, host_spans, default_host)] += \
                (ge - gs) / 1e9 / n_dev
    by_fn: Dict[str, Dict[str, List[float]]] = defaultdict(dict)
    for name, runs in programs.items():
        by_fn[name.split("(", 1)[0]][name] = runs
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_s / n_dev,
        "devices": n_dev,
        "kernel_s": kernel_s / n_dev,
        "collective_s": collective_s / n_dev,
        "exposed_collective_s": exposed_s / n_dev,
        # {function: {program: [seconds of each run, per device plane]}}
        "programs": {fn: dict(p) for fn, p in by_fn.items()},
        "device_ops": [[k, v] for k, v in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[k, v] for k, v in sorted(
            gaps_by.items(), key=lambda kv: -kv[1])[:10]],
    }


def _host_label(gs: float, ge: float, host_spans, default: str) -> str:
    """The host annotation that covers most of the idle gap [gs, ge)."""
    best, best_cover = default, 0.0
    cover: Counter = Counter()
    for s, e, name in host_spans:
        if s >= ge:
            break
        if e > gs:
            cover[name] += min(e, ge) - max(s, gs)
    for name, c in cover.items():
        if c > best_cover:
            best, best_cover = name, c
    return best


def program_runs(reduced: Dict[str, object], fn: str
                 ) -> Dict[str, List[float]]:
    return dict(reduced["programs"].get(fn, {}))    # type: ignore


def split_decode_prefill(reduced: Dict[str, object], fn: str = "jit_fwd"):
    """The engine's one jitted forward serves decode ([max_batch, 1]) and
    every prefill bucket ([1, bucket]); the trace tells them apart only by
    fingerprint.  The program run most often in a serving window is the
    decode step (one run per engine step); the others are prefills (one
    run per admitted request, spread over the buckets).
    Returns (decode runs, prefill runs), seconds each."""
    runs = program_runs(reduced, fn)
    if not runs:
        return [], []
    decode = max(runs, key=lambda k: len(runs[k]))
    prefill = [t for k, v in runs.items() if k != decode for t in v]
    return runs[decode], prefill
