"""What the program's own names say about a run: the engine's phase
counters, and a second pass over the profiler trace with the host
annotations (``llm.*``, ``train.*``), the kernels' names and the
operations' scopes that the program carries since PR 24.  Shared by the
per-layer readers that PR added (benchmark/layer_metrics/); ``trace.py``
and ``readers.py`` are used as they are.

Read by hand off a v5e trace (PR 24's probe, chiprun_out/probe):

- a ``spans.annotate`` annotation is an event of the ``/host:CPU`` plane
  under its own name, its tags the event's stats (``bucket`` of an
  ``llm.prefill``), on the clock of the device planes;
- a Pallas kernel's ``name`` is its HLO instruction's name:
  ``%flash_fwd.4 = ... custom-call(...)``, so ``XLA Ops`` tells forward,
  dq and dkv apart;
- an operation's ``jax.named_scope`` path is NOT in the event's name or
  stats.  It is the ``tf_op`` stat of the event's METADATA
  (``jit(step)/loss_and_grad/transpose(jvp(GPT2))/.../h_0/attn.core/
  flash_dq/flash_dq/pallas_call``), which ``jax.profiler.ProfileData``
  does not show: ``op_scopes`` reads it from the file's wire format (the
  fields are listed in tests/xplane_writer.py and tests/xplane_stats.py).

A program without these names (the parent of PR 24) gives every reader
nothing to read: each returns None and raises nothing.
"""

from __future__ import annotations

import bisect
import re
import sys
import traceback
from collections import defaultdict
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

from . import readers, trace as T
from .stats import median

LLM_LEAVES = (
    "llm.cancel", "llm.admit",
    "llm.prefill.pack", "llm.prefill.run", "llm.prefill.fetch",
    "llm.prefill.sample",
    "llm.decode.pages", "llm.decode.pack", "llm.decode.run",
    "llm.decode.fetch", "llm.decode.sample",
    "llm.publish")
# The three parts of an engine step; llm.other (step_s less the leaves)
# belongs to the schedule.
SAMPLE = ("llm.decode.sample", "llm.prefill.sample")
DEVICE_WAIT = ("llm.decode.run", "llm.decode.fetch",
               "llm.prefill.run", "llm.prefill.fetch")
SCHEDULE = tuple(n for n in LLM_LEAVES if n not in SAMPLE + DEVICE_WAIT)
LLM_SPANS = LLM_LEAVES + ("llm.step", "llm.decode", "llm.prefill",
                          "llm.idle", "llm.compile")
TRAIN_SPANS = ("train.report", "train.report.observe", "train.report.push",
               "train.input.wait", "train.input.transfer",
               "train.step.dispatch", "train.step.compile")
KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")
# Scopes an operation is filed under in the `info` line: the innermost of
# these on its path (models/gpt2.py, train/train_step.py, kv_cache.py).
SCOPES = ("embed", "ln_1", "attn.qkv", "attn.core", "attn.out", "ln_2",
          "mlp", "ln_f", "lm_head", "loss", "optimizer", "grad_norm")


def quiet(fn: Callable) -> Callable:
    """A reader's boundary: whatever goes wrong inside, the run still
    prints its result line, without this metric."""
    def guarded(ctx):
        try:
            return fn(ctx)
        except Exception:   # noqa: BLE001 — reported, metric left out
            print(f"benchmark: reader {fn.__name__} failed:",
                  file=sys.stderr)
            traceback.print_exc(file=sys.stderr)
            return None
    guarded.__name__ = fn.__name__
    return guarded


def note(ctx, key: str, value: Any) -> None:
    """Into the run's `info` line (`detail`), beside the result."""
    if not isinstance(ctx.get("info"), dict):
        ctx["info"] = {}
    ctx["info"].setdefault("phases", {})[key] = value


# ------------------------------------------------------ engine counters

def engine_split_ms(ctx) -> Optional[Dict[str, float]]:
    """Milliseconds per engine step over the window, from the deltas of
    ``stats()["phase_s"]``: sample + schedule + device_wait is the
    engine's own ``step_s`` per step."""
    serve = ctx.get("serve") or {}
    a, b = serve.get("before") or {}, serve.get("at_end") or {}
    if "phase_s" not in a or "phase_s" not in b:
        return None
    steps = b["steps"] - a["steps"]
    if steps <= 0:
        return None
    d = {k: b["phase_s"][k] - a["phase_s"].get(k, 0.0)
         for k in b["phase_s"]}
    d["llm.other"] = (b["step_s"] - a["step_s"]) - sum(d.values())
    per = {k: 1e3 * v / steps for k, v in d.items()}
    out = {"sample": sum(per.get(k, 0.0) for k in SAMPLE),
           "device_wait": sum(per.get(k, 0.0) for k in DEVICE_WAIT),
           "schedule": sum(per.get(k, 0.0) for k in SCHEDULE)
           + per["llm.other"]}
    note(ctx, "engine_step_ms", {
        **out, "step": 1e3 * (b["step_s"] - a["step_s"]) / steps,
        "steps": steps, "leaves": per,
        "prefills": b.get("prefills", 0) - a.get("prefills", 0),
        "compiles": b.get("compiles", 0) - a.get("compiles", 0)})
    return out


# ------------------------------------------------------ the trace again

def again(ctx) -> Optional[T.Trace]:
    """The run's trace loaded once more, with the program's annotations
    as its host events."""
    if not ctx.get("trace_path"):
        return None
    if "_phases_trace" not in ctx:
        ctx["_phases_trace"] = T.load(ctx["trace_path"],
                                      LLM_SPANS + TRAIN_SPANS)
    return ctx["_phases_trace"]


def _spans(tr: T.Trace, name: str) -> List[T.Interval]:
    return sorted((s, s + d) for n, s, d in tr.host if n == name)


def annotation_tags(path: str, name: str
                    ) -> List[Tuple[float, float, Dict[str, Any]]]:
    """(start_ns, end_ns, tags) of every host annotation ``name``."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend((e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats))
                       for e in line.events if e.name == name)
    return sorted(out, key=lambda x: x[0])


# ---- the file's wire format, for what ProfileData leaves out

def _varint(b: bytes, i: int) -> Tuple[int, int]:
    n = shift = 0
    while True:
        c = b[i]
        i += 1
        n |= (c & 0x7F) << shift
        shift += 7
        if not c & 0x80:
            return n, i


def _fields(b: bytes) -> Iterator[Tuple[int, Any]]:
    """(field number, value) of one protobuf message: an int for a
    varint, bytes for a length-delimited or fixed-width field."""
    i = 0
    while i < len(b):
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = b[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = b[i:i + n], i + n
        else:
            raise ValueError(f"wire type {wire} in an XSpace")
        yield key >> 3, v


def _map_entry(b: bytes) -> Tuple[int, bytes]:
    kv = dict(_fields(b))
    return kv.get(1, 0), kv.get(2, b"")


def op_scopes(path: str, stat: str = "tf_op") -> Dict[str, str]:
    """{event name: scope path} for the device planes' operations: the
    ``tf_op`` stat of each XEventMetadata (XPlane.event_metadata = 4,
    XEventMetadata {name = 2, stats = 5}, XStat {metadata_id = 1,
    str_value = 5, ref_value = 7}, XPlane.stat_metadata = 5)."""
    with open(path, "rb") as f:
        space = f.read()
    out: Dict[str, str] = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, metas, stat_names = "", [], {}
        for f2, v in _fields(plane):
            if f2 == 2:
                name = v.decode(errors="replace")
            elif f2 == 4:
                metas.append(_map_entry(v)[1])
            elif f2 == 5:
                sid, meta = _map_entry(v)
                stat_names[sid] = dict(_fields(meta)).get(
                    2, b"").decode(errors="replace")
        if not T.DEVICE_PLANE.match(name):
            continue
        for meta in metas:
            ev_name, scope = "", None
            for f3, v in _fields(meta):
                if f3 == 2:
                    ev_name = v.decode(errors="replace")
                elif f3 == 5:
                    st = dict(_fields(v))
                    if stat_names.get(st.get(1)) != stat:
                        continue
                    if 5 in st:
                        scope = st[5].decode(errors="replace")
                    elif 7 in st:
                        scope = stat_names.get(st[7])
            if ev_name and scope:
                out[ev_name] = scope
    return out


def scope_parts(scope_path: str) -> List[str]:
    """The names on an operation's scope path, outermost first, with the
    transformations' wrappers taken off: ``transpose(jvp(loss))`` is
    ``loss``."""
    parts = []
    for comp in scope_path.split("/"):
        m = re.fullmatch(r"(?:\w+\()*([^():]*)\)*:?", comp)
        parts.append(m.group(1) if m else comp)
    return parts


def filed_under(scope_path: Optional[str]) -> str:
    """The one label an operation's time is filed under in `info`."""
    if not scope_path:
        return "(no scope)"
    parts = scope_parts(scope_path)
    for p in reversed(parts):
        if p in SCOPES or re.fullmatch(r"ln_\w+", p):
            return p
    return "loss_and_grad (other)" if "loss_and_grad" in parts \
        else "(other scope)"


# -------------------------------------------------------------- serving

def _covering(spans: List[T.Interval], t: float) -> Optional[int]:
    """Index of the span of a sorted, non-overlapping list that holds
    ``t``."""
    i = bisect.bisect_right(spans, (t, float("inf"))) - 1
    return i if i >= 0 and spans[i][0] <= t <= spans[i][1] else None


def serve_capture(ctx) -> Optional[Dict[str, Any]]:
    """The capture of a serving run by the engine's annotations: device
    idle time per engine step under each leaf, and every ``jit_fwd`` run
    classed by the clock (inside an ``llm.decode``: a decode step;
    inside an ``llm.prefill``: a prefill of that annotation's bucket; under
    neither: with the other runs of its program, if the clock classed any).

    A gap's time goes to each leaf for the part of the gap the leaf
    covers (one gap here spans the whole host part of a step, fetch to
    launch, so the rule of ``trace.reduce``, all of a gap to the span
    that covers most of it, would name one leaf for all of it)."""
    if "_phases_serve" in ctx:
        return ctx["_phases_serve"]
    ctx["_phases_serve"] = out = _serve_capture(ctx)
    if out:
        note(ctx, "capture", out)
    return out


def _serve_capture(ctx) -> Optional[Dict[str, Any]]:
    tr = again(ctx)
    steps = _spans(tr, "llm.step") if tr else []
    if not steps or not tr.devices:
        return None
    lo, hi = T.window_of(tr)
    # Steps in the capture: the window over the steps' period.  (A step
    # under way when the capture starts has left no annotation, but its
    # device run and its idle time are in the window.)
    period = (steps[-1][0] - steps[0][0]) / (len(steps) - 1) \
        if len(steps) > 1 else steps[0][1] - steps[0][0]
    if period <= 0:
        return None
    n_steps = (hi - lo) / period
    dev = tr.devices[0]
    busy = T.union(T.clip(((s, s + d) for _, s, d in dev.ops), lo, hi))
    gaps = T.subtract([(lo, hi)], busy)
    idle: Dict[str, float] = {}
    for leaf in LLM_LEAVES + ("llm.idle",):
        covered = T.union(T.clip(_spans(tr, leaf), lo, hi))
        if leaf == "llm.admit":     # its own time: the prefills apart
            covered = T.subtract(covered, T.union(_spans(tr, "llm.prefill")))
        idle[leaf] = T.total(gaps) - T.total(T.subtract(gaps, covered))
    idle["(no leaf)"] = T.total(gaps) - sum(idle.values())
    fetch_s = sum(idle[k] for k in DEVICE_WAIT) / 1e9
    idle_s = T.total(gaps) / 1e9

    decodes = _spans(tr, "llm.decode")
    prefills = annotation_tags(ctx["trace_path"], "llm.prefill")
    prefill_spans = [(s, e) for s, e, _ in prefills]
    decode_runs: List[float] = []
    by_bucket: Dict[str, List[float]] = defaultdict(list)
    unclassed: List[Tuple[str, float]] = []
    class_of: Dict[str, List[float]] = {}       # program -> its runs' list
    for name, s, d in dev.modules:
        if name.split("(", 1)[0] != "jit_fwd" or not lo <= s + d / 2 <= hi:
            continue
        i = _covering(prefill_spans, s)
        if _covering(decodes, s) is not None:
            runs = decode_runs
        elif i is not None:
            runs = by_bucket[str(prefills[i][2].get("bucket", "?"))]
        else:
            unclassed.append((name, d / 1e9))
            continue
        runs.append(d / 1e9)
        class_of[name] = runs
    # The step under way when the capture starts left its device run but
    # no annotation: such a run goes with the other runs of its program.
    other_runs = [t for name, t in unclassed if name not in class_of]
    for name, t in unclassed:
        if name in class_of:
            class_of[name].append(t)
    return {
        "window_s": (hi - lo) / 1e9, "idle_s": idle_s,
        "steps": n_steps,
        "idle_fetch_ms": 1e3 * fetch_s / n_steps,
        "idle_host_ms": 1e3 * (idle_s - fetch_s) / n_steps,
        "idle_ms_by_leaf": {k: 1e3 * v / 1e9 / n_steps
                            for k, v in idle.items() if v},
        "decode_runs": len(decode_runs),
        "decode_ms": 1e3 * sum(decode_runs) / len(decode_runs)
        if decode_runs else None,
        "prefill_runs": {b: len(v) for b, v in sorted(by_bucket.items())},
        "prefill_ms_by_bucket": {b: 1e3 * sum(v) / len(v)
                                 for b, v in sorted(by_bucket.items())},
        "prefill_ms": [1e3 * t for v in by_bucket.values() for t in v],
        "unclassed_runs": len(other_runs),
    }


# ------------------------------------------------------------- training

def train_capture(ctx) -> Optional[Dict[str, Any]]:
    """Device milliseconds per traced step (averaged over the chips) of
    each named kernel and under each scope, ``copy`` by scope, and the
    ``train.report`` annotations' medians."""
    if "_phases_train" in ctx:
        return ctx["_phases_train"]
    ctx["_phases_train"] = out = _train_capture(ctx)
    if out:
        note(ctx, "capture", out)
    return out


def _train_capture(ctx) -> Optional[Dict[str, Any]]:
    tr = again(ctx)
    n = readers.traced_steps(ctx) if ctx.get("train") else 0
    if not tr or not tr.devices or not n:
        return None
    lo, hi = T.window_of(tr)
    scopes = op_scopes(ctx["trace_path"])
    per = 1e3 / 1e9 / len(tr.devices) / n     # ns -> ms a step a chip
    kernels: Dict[str, float] = defaultdict(float)
    by_scope: Dict[str, float] = defaultdict(float)
    within: Dict[str, float] = defaultdict(float)
    copies: Dict[str, float] = defaultdict(float)
    for dev in tr.devices:
        for name, s, e in T._leaves(dev, lo, hi):
            scope = scopes.get(name)
            ms = (e - s) * per
            if T.is_kernel(name):
                kernels[_kernel_of(name, scope)] += ms
            by_scope[filed_under(scope)] += ms
            for part in set(scope_parts(scope)) if scope else ():
                within[part] += ms
            if T.opcode(name) == "copy":
                copies[filed_under(scope)] += ms

    def med_ms(span: str) -> Optional[float]:
        inside = [e - s for s, e in _spans(tr, span) if lo <= s and e <= hi]
        return median(inside) / 1e6 if inside else None

    return {
        "steps": n, "devices": len(tr.devices),
        "kernel_ms": dict(kernels),
        "scoped": bool(scopes),
        "loss_ms": within.get("loss"),
        "optimizer_ms": within.get("optimizer"),
        "ms_by_scope": dict(sorted(by_scope.items(), key=lambda kv: -kv[1])),
        "copy_ms_by_scope": dict(sorted(copies.items(),
                                        key=lambda kv: -kv[1])),
        "report_ms": med_ms("train.report"),
        "report_observe_ms": med_ms("train.report.observe"),
        "report_push_ms": med_ms("train.report.push"),
        "input_wait_ms": med_ms("train.input.wait"),
        "dispatch_ms": med_ms("train.step.dispatch"),
    }


def _kernel_of(op_name: str, scope: Optional[str]) -> str:
    """A kernel call's name: its instruction's (``%flash_dq.3``), else
    the innermost scope that names a kernel, else the instruction's."""
    base = re.sub(r"\.\d+$", "", op_name.split(" ", 1)[0].lstrip("%"))
    if base not in KERNELS and scope:
        for part in reversed(scope_parts(scope)):
            if part in KERNELS:
                return part
    return base


def kernel_ms(ctx, kernel: str) -> Optional[float]:
    cap = train_capture(ctx)
    return cap["kernel_ms"].get(kernel) if cap else None


def scope_ms(ctx, key: str) -> Optional[float]:
    cap = train_capture(ctx)
    return cap[key] if cap and cap["scoped"] else None
