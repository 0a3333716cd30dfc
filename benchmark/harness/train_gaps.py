"""Where the device waited in a traced training window, gap by gap (PR 35).

``trace.reduce`` says how long the device idled and gives each WHOLE gap
to the one host annotation that covers most of it; ``trainer.host_ms`` is
wall time less busy time.  Neither says whether the device waited for a
late launch, a late return, or inside its own program, nor whether the
idle is every step's or one stall.  This reader splits the window's idle
time at the program's own boundaries:

    run N ............ | gap ................................ | run N+1
    first op .. last op| return | report | wait | own | launch |first op

- a *run* is one run of the step program (the program of ``XLA Modules``
  run most often in the window), from its first to its last operation of
  ``XLA Ops``; idle INSIDE it is the compiler's and the kernels';
- the *gap* between two runs is split among: ``return`` (run N's last
  operation to the start of step N's ``train.report``: how long after the
  device finished the loop had its loss and moved on), ``report`` (that
  annotation), ``input_wait`` (``train.input.wait``), ``launch`` (the
  start of the next run's ``train.step.dispatch`` to its first
  operation) and ``own`` (what is left: the loop's own code), each as the
  idle time it covers.  They sum to the gap;
- the window's edges (its start to the first run, the last run to its
  end) are together one more gap, so inside + between = the window's idle.

A run is paired with the ``train.step.dispatch`` that launched it by the
clock (the one that started nearest the run's first operation: in a loop
that reads its loss every step the next one is a whole step away, and a
capture's host and device clocks differ by up to ~1 ms, so "the last
before" would at times name the step before), and with ITS step's
``train.report`` by the tag ``step`` both carry since PR 35.  Where the
dispatch seems to start after the run, ``launch`` reads 0 and ``return``
takes its share: the gap and the parts' sum do not depend on that clock.  A program without the tag is paired by position, its lists go to
the ``info`` line all the same, and the three readers that rest on the
pairing report nothing.  On several chips every per-step quantity is the
mean over the device planes, as ``step.device_ms``.
"""

from __future__ import annotations

import bisect
from collections import Counter
from typing import Any, Dict, List, Optional, Tuple

from . import phases, trace as T
from .stats import median

PARTS = ("return", "report", "input_wait", "own", "launch")
KEYS = ("gap",) + PARTS + ("dispatch",)     # of one gap's entry
TAGGED = ("train.step.dispatch", "train.report")
NAMES = TAGGED + ("train.input.wait", "train.input.transfer")


def host_events(path: str) -> Dict[str, List[Tuple[float, float, Any]]]:
    """{name: [(start_ns, end_ns, step tag or None)]} of the training
    annotations, in one pass over the file."""
    from jax.profiler import ProfileData

    out: Dict[str, list] = {name: [] for name in NAMES}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in out:
                    tag = dict(e.stats).get("step") \
                        if e.name in TAGGED else None
                    out[e.name].append((e.start_ns,
                                        e.start_ns + e.duration_ns, tag))
    return {name: sorted(v, key=lambda x: x[0]) for name, v in out.items()}


def _covered(gaps: List[T.Interval], spans: List[T.Interval]) -> float:
    """Nanoseconds of the idle intervals ``gaps`` that ``spans`` cover."""
    return T.total(gaps) - T.total(T.subtract(gaps, T.union(spans)))


def _plane(dev: T.DevicePlane, lo: float, hi: float, host
           ) -> Optional[Dict[str, Any]]:
    """One device plane's runs, gaps and their parts, in nanoseconds."""
    inside_window = [(n, s, s + d) for n, s, d in dev.modules
                     if lo <= s + d / 2 <= hi]
    if not inside_window:
        return None
    program = Counter(n for n, _, _ in inside_window).most_common(1)[0][0]
    modules = sorted((s, e) for n, s, e in inside_window if n == program)
    starts = [s for s, _ in modules]
    extent: List[Optional[List[float]]] = [None] * len(modules)
    leaves = list(T._leaves(dev, lo, hi))
    for _, s, e in leaves:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s <= modules[i][1]:
            x = extent[i]
            if x is None:
                extent[i] = [s, e]
            else:
                x[0], x[1] = min(x[0], s), max(x[1], e)
    runs = [tuple(x) if x else modules[i] for i, x in enumerate(extent)]
    busy = T.union((s, e) for _, s, e in leaves)

    def idle(a: float, b: float) -> List[T.Interval]:
        return T.subtract([(a, b)], busy) if b > a else []

    dispatches, reports = host["train.step.dispatch"], host["train.report"]
    d_starts = [s for s, _, _ in dispatches]
    report_of = {tag: (s, e) for s, e, tag in reports if tag is not None}
    tagged = bool(report_of) and all(t is not None for _, _, t in dispatches)
    steps, launched_by = [], []
    for k, (a, _) in enumerate(runs):
        # the dispatch that started NEAREST the run's first operation:
        # a capture's host and device clocks differ by up to ~1 ms, a
        # launch's own length, so it may seem to start after the run
        j = bisect.bisect_right(d_starts, a)
        near = min((i for i in (j - 1, j) if 0 <= i < len(dispatches)),
                   key=lambda i: abs(d_starts[i] - a), default=None)
        launched_by.append(None if near is None else dispatches[near])
        steps.append(launched_by[-1][2] if tagged and near is not None
                     else k)
    if not tagged:      # the parent: the k-th report in the window
        inside = [(s, e) for s, e, _ in reports if lo <= s <= hi]
        report_of = dict(enumerate(inside))
    waits = [(s, e) for s, e, _ in host["train.input.wait"]]

    def split(gap: List[T.Interval], a: float, b: float, before: int,
              after: Optional[int]) -> Dict[str, float]:
        """The idle intervals ``gap`` inside [a, b) by part; ``before``
        is the run that ended at ``a`` (-1: the window's start),
        ``after`` the one that starts at ``b`` (None: its end)."""
        spans: Dict[str, List[T.Interval]] = {p: [] for p in PARTS}
        report = report_of.get(steps[before]) if before >= 0 else None
        if report and report[0] < b:
            spans["return"] = [(a, min(report[0], b))]
            spans["report"] = [report]
        d = launched_by[after] if after is not None else None
        if d and d[0] < b:
            spans["launch"] = [(max(d[0], a), b)]
        spans["input_wait"] = T.subtract(
            T.union(T.clip(waits, a, b)),
            T.union(spans["return"] + spans["report"] + spans["launch"]))
        out = {p: _covered(gap, spans[p]) for p in PARTS if p != "own"}
        out["own"] = T.total(gap) - _covered(
            gap, [i for p in PARTS for i in spans[p]])
        # the part of the launch under the annotation itself: the call
        # into the executable; the rest is the runtime's way to the chip
        out["dispatch"] = _covered(gap, T.clip([d[:2]], a, b)) if d else 0.0
        return out

    between = []
    for k in range(1, len(runs)):
        a, b = runs[k - 1][1], runs[k][0]
        gap = idle(a, b)
        between.append({"step": steps[k], "gap": T.total(gap),
                        **split(gap, a, b, k - 1, k)})
    head, tail = idle(lo, runs[0][0]), idle(runs[-1][1], hi)
    edge = {"gap": T.total(head) + T.total(tail)}
    h = split(head, lo, runs[0][0], -1, 0)
    t = split(tail, runs[-1][1], hi, len(runs) - 1, None)
    edge.update({p: h[p] + t[p] for p in KEYS[1:]})
    return {
        "program": program, "tagged": tagged, "steps": steps,
        "inside": [T.total(idle(a, b)) for a, b in runs],
        "between": between, "edge": edge, "head": T.total(head),
        "tail": T.total(tail), "idle": T.total(idle(lo, hi)),
    }


def capture(ctx) -> Optional[Dict[str, Any]]:
    """The window's idle time by step and by part, milliseconds, every
    per-step number the mean over the device planes.  None where the
    trace holds no run of a step program; noted in the `info` line."""
    if "_train_gaps" not in ctx:
        ctx["_train_gaps"] = out = _capture(ctx)
        if out:
            phases.note(ctx, "train_gaps", out)
    return ctx["_train_gaps"]


def _capture(ctx) -> Optional[Dict[str, Any]]:
    tr = phases.again(ctx)
    if not tr or not tr.devices or not ctx.get("train"):
        return None
    lo, hi = T.window_of(tr)
    host = host_events(ctx["trace_path"])
    planes = [p for p in (_plane(dev, lo, hi, host) for dev in tr.devices)
              if p]
    if not planes or any(p["steps"] != planes[0]["steps"] for p in planes):
        return None         # no run, or the chips ran different steps
    first, n = planes[0], len(planes[0]["steps"])

    def over_planes(pick) -> float:     # ns on each plane -> mean, ms
        return sum(pick(p) for p in planes) / len(planes) / 1e6

    by_step = [{"step": first["between"][k]["step"],
                **{key: over_planes(lambda p: p["between"][k][key])
                   for key in KEYS}}
               for k in range(n - 1)]
    edge = {key: over_planes(lambda p: p["edge"][key]) for key in KEYS}
    inside = [over_planes(lambda p: p["inside"][k]) for k in range(n)]
    gaps = [g["gap"] for g in by_step]
    transfers = [(e - s) / 1e6 for s, e, _ in host["train.input.transfer"]
                 if lo <= s <= hi]
    waits = [(e - s) / 1e6 for s, e, _ in host["train.input.wait"]
             if lo <= s <= hi]
    between_ms = sum(gaps) + edge["gap"]
    idle_ms = over_planes(lambda p: p["idle"])
    reduced = ctx.get("trace") or {}
    out = {
        "program": first["program"], "devices": len(planes), "runs": n,
        "paired_by": "step" if first["tagged"] else "position",
        # one entry a whole gap: the device idle BEFORE that step's run
        "gap_ms_by_step": by_step,
        "gap_ms": median(gaps) if gaps else None,
        "gap_mean_ms": sum(gaps) / len(gaps) if gaps else None,
        "parts_ms": {p: median([g[p] for g in by_step])
                     for p in KEYS[1:]} if gaps else None,
        # the window's start to the first run and the last run to its
        # end: together the one gap the window cuts in two
        "edge_ms": {**edge, "head": over_planes(lambda p: p["head"]),
                    "tail": over_planes(lambda p: p["tail"])},
        "inside_ms_by_run": inside,
        "inside_ms": sum(inside) / n,
        # does the program name its own phases (PR 24 and later)?
        "named": bool(host["train.step.dispatch"]),
        "input_wait_ms": sum(waits) / n,
        "input_waits": len(waits),
        "input_transfer_ms": median(transfers) if transfers else None,
        "input_transfers": len(transfers),
        # identity 1: every gap's parts against the gap (worst, ms)
        "parts_less_gap_ms": max(
            (abs(sum(g[p] for p in PARTS) - g["gap"])
             for g in by_step + [edge]), default=0.0),
        # identity 2: inside + between against the window's idle time
        "idle_ms": {"inside": sum(inside), "between": between_ms,
                    "window": idle_ms},
    }
    if reduced.get("window_s"):     # trace.reduce's own reckoning
        theirs = 1e3 * (reduced["window_s"] - reduced["busy_s"])
        out["idle_ms"]["window_by_reduce"] = theirs
        out["idle_ms"]["ratio"] = (sum(inside) + between_ms) / theirs \
            if theirs else None
    return out


def tagged(ctx) -> Optional[Dict[str, Any]]:
    """``capture`` where runs, dispatches and reports were paired by the
    program's ``step`` tag and there is a whole gap to read."""
    cap = capture(ctx)
    return cap if cap and cap["paired_by"] == "step" and cap["parts_ms"] \
        else None


# --------------------------------------------------- the engine's clocks

def engine_offcpu_ms(ctx) -> Optional[float]:
    """Per engine step: wall time less the engine thread's CPU time, the
    two ``.fetch`` leaves apart (their waiting is the device's).  From
    the window's deltas of ``stats()``: ``phase_cpu_s`` / ``step_cpu_s``
    against ``cpu_sample`` (the wall clock over the steps whose CPU time
    the engine read: one in sixteen); by leaf in `info`."""
    serve = ctx.get("serve") or {}
    a, b = serve.get("before") or {}, serve.get("at_end") or {}
    if "cpu_sample" not in a or "cpu_sample" not in b:
        return None
    steps = b["cpu_sample"]["steps"] - a["cpu_sample"]["steps"]
    if steps <= 0:
        return None
    per = 1e3 / steps
    wall, cpu = ({k: per * (y[k] - x[k]) for k in y} for x, y in (
        (a["cpu_sample"]["phase_s"], b["cpu_sample"]["phase_s"]),
        (a["phase_cpu_s"], b["phase_cpu_s"])))
    step_wall = per * (b["cpu_sample"]["step_s"] - a["cpu_sample"]["step_s"])
    step_cpu = per * (b["step_cpu_s"] - a["step_cpu_s"])
    wall["llm.other"] = step_wall - sum(wall.values())
    cpu["llm.other"] = step_cpu - sum(cpu.values())
    off = {k: wall[k] - cpu[k] for k in wall}
    fetch = sum(v for k, v in off.items() if k.endswith(".fetch"))
    value = (step_wall - step_cpu) - fetch
    phases.note(ctx, "engine_offcpu_ms", {
        "value": value, "steps_sampled": steps,
        "steps": b["steps"] - a["steps"], "step_ms": step_wall,
        "step_cpu_ms": step_cpu, "fetch_offcpu_ms": fetch,
        "wall_ms": wall, "cpu_ms": cpu, "offcpu_ms": off})
    return value
