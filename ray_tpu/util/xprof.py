"""Compiled-program performance introspection — the data behind
``rt perf`` and the dashboard's ``/api/perf`` route.

Any jitted step can be lowered and AOT-compiled (``fn.lower(*args)
.compile()``); the resulting executable carries the static truth about
the program XLA actually runs: ``cost_analysis()`` flops and bytes
accessed, ``memory_analysis()`` argument/output/temp sizes, and the
post-SPMD optimized HLO text whose collective ops (all-reduce /
all-gather / reduce-scatter / all-to-all) name their replica groups
(a collective-permute names pairs of devices: counted by kind only).
This module harvests those numbers (``register_compiled``), attributes
each collective to the mesh axes its replica groups span, and combines
the static program facts with measured step time into a roofline
report: achieved vs attainable FLOP/s at the program's arithmetic
intensity, and per-axis collective byte/time shares.

Layering matters here: everything above the "jax layer" marker is
plain Python over plain dicts — no jax, no aiohttp, no cluster (the
ops-box import guard in tests/test_xprof.py) — so ``rt perf`` runs on
a box without the ML stack.  The jax-facing entry points import jax
lazily inside the function body and never raise into a training or
request path.
"""

from __future__ import annotations

import contextlib
import re
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from . import chips

# ------------------------------------------------------------------
# Peak rates: one table, keyed by device_kind, in util/chips.py
# (jax-free).  ``device_kind`` defaults to this process's own device;
# an ops box that has none passes the kind its telemetry reported.
def resolve_peak_flops(device_kind: Optional[str] = None) -> float:
    return chips.resolve_peak("flops_per_sec",
                              "RT_PEAK_FLOPS_PER_DEVICE", device_kind)


def resolve_peak_hbm(device_kind: Optional[str] = None) -> float:
    return chips.resolve_peak("hbm_bytes_per_sec",
                              "RT_PEAK_HBM_BYTES_PER_SEC", device_kind)


def resolve_interconnect(device_kind: Optional[str] = None) -> float:
    return chips.resolve_peak("ici_bytes_per_sec",
                              "RT_INTERCONNECT_BYTES_PER_SEC",
                              device_kind)


# ------------------------------------------------------------------
# Roofline math.

def roofline(flops: float, bytes_accessed: float, peak_flops: float,
             peak_bytes_per_sec: float) -> Dict[str, float]:
    """Classic roofline position of one program: arithmetic intensity
    (flops per HBM byte), the attainable FLOP/s ceiling at that
    intensity (min of the compute roof and the bandwidth roof), and
    the ridge point where the two roofs meet."""
    intensity = flops / bytes_accessed if bytes_accessed > 0 else 0.0
    ridge = peak_flops / peak_bytes_per_sec \
        if peak_bytes_per_sec > 0 else 0.0
    attainable = min(peak_flops, intensity * peak_bytes_per_sec) \
        if intensity > 0 else 0.0
    min_time_s = flops / attainable if attainable > 0 else 0.0
    return {
        "flops": flops,
        "bytes": bytes_accessed,
        "intensity": intensity,
        "ridge_intensity": ridge,
        "attainable_flops_per_sec": attainable,
        "bound": "compute" if intensity >= ridge and ridge > 0
        else "memory",
        "min_time_s": min_time_s,
    }


# ------------------------------------------------------------------
# HLO collective parsing.

COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "all-to-all", "collective-permute")

_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "u2": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "f32": 4, "s32": 4, "u32": 4,
    "f64": 8, "s64": 8, "u64": 8, "c64": 8, "c128": 16,
}

# `= <result type> <op>(` — the result type is either one array type
# (dtype[dims]{layout}) or a tuple of them; matching on the definition
# form keeps operand *references* to a collective (e.g. a fusion
# consuming %all-reduce) from double counting.
_INSTR_RE = re.compile(
    r"=\s*(?P<type>\((?:[^()]|\([^()]*\))*\)"
    r"|[a-z][a-z0-9]*\[[0-9,]*\](?:\{[^}]*\})?)\s*"
    r"(?P<op>" + "|".join(COLLECTIVE_OPS) + r")"
    r"(?P<suffix>-start|-done)?(?:\.\d+)?\(")

# replica_groups: explicit `{{0,1},{2,3}}` or iota-v2
# `[groups,size]<=[d0,d1,...]` with an optional transpose `T(perm)`.
_GROUPS_RE = re.compile(
    r"replica_groups=(?P<explicit>\{(?:\{[0-9,\s]*\},?\s*)*\}"
    r"|\[[0-9,]+\]<=\[[0-9,]+\](?:T\([0-9,]+\))?)")

_ARRAY_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")


def _dtype_bytes(dtype: str) -> int:
    if dtype in _DTYPE_BYTES:
        return _DTYPE_BYTES[dtype]
    if dtype.startswith("f8") or dtype.startswith("e4") \
            or dtype.startswith("e5"):
        return 1
    return 4


def _shape_bytes(type_str: str) -> float:
    """Total byte size of one HLO result type (array or tuple)."""
    total = 0.0
    for m in _ARRAY_RE.finditer(type_str):
        dims = m.group(2)
        n = 1
        for d in dims.split(","):
            if d.strip():
                n *= int(d)
        total += n * _dtype_bytes(m.group(1))
    return total


def _prod(vals) -> int:
    out = 1
    for v in vals:
        out *= int(v)
    return out


def _iota_group_ids(dims: List[int],
                    perm: Optional[List[int]]) -> List[int]:
    """Device ids of `iota(dims)` transposed by `perm`, flattened
    row-major — the id stream the iota replica-group format chunks."""
    strides = [1] * len(dims)
    for i in range(len(dims) - 2, -1, -1):
        strides[i] = strides[i + 1] * dims[i + 1]
    if perm is None:
        perm = list(range(len(dims)))
    tdims = [dims[p] for p in perm]
    tstrides = [strides[p] for p in perm]
    out: List[int] = []

    def rec(k: int, off: int) -> None:
        if k == len(tdims):
            out.append(off)
            return
        for i in range(tdims[k]):
            rec(k + 1, off + i * tstrides[k])

    rec(0, 0)
    return out


def parse_replica_groups(text: str) -> List[List[int]]:
    """Both HLO replica-group syntaxes -> explicit group lists."""
    text = text.strip()
    if text.startswith("{"):
        return [[int(x) for x in inner.split(",") if x.strip()]
                for inner in re.findall(r"\{([0-9,\s]*)\}", text)
                if inner.strip()]
    m = re.match(r"\[([0-9,]+)\]<=\[([0-9,]+)\]"
                 r"(?:T\(([0-9,]+)\))?", text)
    if not m:
        return []
    gshape = [int(x) for x in m.group(1).split(",")]
    dims = [int(x) for x in m.group(2).split(",")]
    perm = [int(x) for x in m.group(3).split(",")] \
        if m.group(3) else None
    ids = _iota_group_ids(dims, perm)
    num, size = (gshape + [1, 1])[:2]
    return [ids[i * size:(i + 1) * size] for i in range(num)]


def parse_hlo_collectives(hlo_text: str) -> List[Dict[str, Any]]:
    """Every collective op defined in an optimized-HLO dump, with its
    result byte size and replica groups.  ``-done`` halves of async
    pairs are skipped (their ``-start`` already counted)."""
    out: List[Dict[str, Any]] = []
    for line in (hlo_text or "").splitlines():
        m = _INSTR_RE.search(line)
        if not m or m.group("suffix") == "-done":
            continue
        gm = _GROUPS_RE.search(line)
        out.append({
            "op": m.group("op"),
            "bytes": _shape_bytes(m.group("type")),
            "groups": parse_replica_groups(gm.group("explicit"))
            if gm else [],
        })
    return out


# ------------------------------------------------------------------
# Replica-group -> mesh-axis attribution.

def _coords(device: int, sizes: List[int]) -> Tuple[int, ...]:
    out = []
    for s in reversed(sizes):
        out.append(device % s)
        device //= s
    return tuple(reversed(out))


def attribute_axes(groups: List[List[int]],
                   axis_sizes: Optional[Dict[str, int]]) -> str:
    """Which mesh axes a collective's replica groups span.

    Replica-group ids index the mesh's flattened (C-order) device
    array — the device_assignment jit builds from ``mesh.devices`` —
    so a device id unravels to mesh coordinates over the ordered
    ``axis_sizes``.  An axis whose coordinate varies within a group is
    an axis the collective communicates over; a group that spans
    several axes at once (e.g. a global all-reduce on a 2D mesh)
    reports the combined ``a+b`` key."""
    if not axis_sizes:
        return "all"
    names = list(axis_sizes)
    sizes = [int(axis_sizes[n]) for n in names]
    total = _prod(sizes)
    varying: set = set()
    for g in groups:
        if any(d < 0 or d >= total for d in g):
            return "unknown"
        cs = [_coords(d, sizes) for d in g]
        for ax in range(len(names)):
            if len({c[ax] for c in cs}) > 1:
                varying.add(ax)
    if not varying:
        return "none"
    return "+".join(names[i] for i in sorted(varying))


def collective_wire_bytes(op: str, result_bytes: float,
                          group_size: int) -> float:
    """Per-device wire bytes under the standard ring conventions,
    computed from the RESULT shape my parser captured: an all-gather's
    result is the gathered (full) array, a reduce-scatter's is the
    scattered shard."""
    g = max(int(group_size), 1)
    if g <= 1:
        return 0.0
    if op == "all-reduce":
        return 2.0 * result_bytes * (g - 1) / g
    if op == "reduce-scatter":
        return result_bytes * (g - 1)
    return result_bytes * (g - 1) / g   # all-gather / all-to-all


def count_collectives(collectives: List[Dict[str, Any]]
                      ) -> Dict[str, int]:
    """How many collectives of each kind a program holds, every kind of
    ``COLLECTIVE_OPS`` present (0 where it has none): a layout that
    makes the partitioner reshard an activation shows here as
    all-to-alls or collective-permutes that were not there before."""
    return {op: sum(c["op"] == op for c in collectives)
            for op in COLLECTIVE_OPS}


def summarize_collectives(collectives: List[Dict[str, Any]],
                          axis_sizes: Optional[Dict[str, int]]
                          ) -> Dict[str, Dict[str, Any]]:
    """Aggregate parsed collectives into per-mesh-axis wire bytes:
    {axis: {"bytes", "ops", "by_op": {op: bytes}}}.  A
    collective-permute names pairs of devices, not replica groups, and
    is left out (``count_collectives`` counts it)."""
    out: Dict[str, Dict[str, Any]] = {}
    world = _prod(axis_sizes.values()) if axis_sizes else 0
    for c in collectives:
        if c["op"] == "collective-permute":
            continue
        groups = c.get("groups") or []
        if not groups and world:
            # Empty replica_groups means one group of every device.
            groups = [list(range(world))]
        axis = attribute_axes(groups, axis_sizes)
        if axis == "none":
            continue
        gsize = max((len(g) for g in groups), default=world or 1)
        wire = collective_wire_bytes(c["op"], c.get("bytes", 0.0),
                                     gsize)
        if wire <= 0:
            continue
        a = out.setdefault(axis, {"bytes": 0.0, "ops": 0, "by_op": {}})
        a["bytes"] += wire
        a["ops"] += 1
        a["by_op"][c["op"]] = a["by_op"].get(c["op"], 0.0) + wire
    return out


# ------------------------------------------------------------------
# Report assembly (pure: programs + measured times in, report out).

def build_report(programs: Dict[str, Dict[str, Any]],
                 measured: Optional[Dict[str, Dict[str, float]]] = None,
                 *, peak_flops: Optional[float] = None,
                 peak_hbm: Optional[float] = None,
                 interconnect: Optional[float] = None,
                 device_kind: Optional[str] = None
                 ) -> Dict[str, Any]:
    """Combine harvested program facts with measured step times.

    Peaks not passed come from the ``RT_PEAK_*`` overrides or the chip
    table row of ``device_kind`` (default: the kind the programs were
    harvested on, else this process's device); an unknown device
    raises ``chips.UnknownChipError``.

    ``programs``: {name: {"flops", "bytes", "memory": {kind: bytes},
    "collectives": {axis: {"bytes", ...}}, "compiles",
    "compile_seconds"}} — flops/bytes are PER DEVICE (cost_analysis of
    the post-SPMD module).  ``measured``: {name: {"step_time_s": ...,
    "achieved_flops_per_sec": ...}} (either key optional).

    Per program the report carries the roofline position, achieved vs
    attainable FLOP/s, and a step decomposition: roofline-minimum
    compute time, per-axis collective minimum time at the interconnect
    bandwidth, and the unattributed remainder of the measured step.
    """
    if not programs:
        return {"ts": time.time(), "peaks": {}, "programs": {}}
    if device_kind is None:
        device_kind = next(
            (p["device_kind"] for p in programs.values()
             if p.get("device_kind")), None)
    peak_flops = peak_flops or resolve_peak_flops(device_kind)
    peak_hbm = peak_hbm or resolve_peak_hbm(device_kind)
    interconnect = interconnect or resolve_interconnect(device_kind)
    measured = measured or {}
    rows: Dict[str, Any] = {}
    for name, prog in sorted(programs.items()):
        flops = float(prog.get("flops") or 0.0)
        bytes_ = float(prog.get("bytes") or 0.0)
        rl = roofline(flops, bytes_, peak_flops, peak_hbm)
        colls = prog.get("collectives") or {}
        total_coll = sum(float(a.get("bytes") or 0.0)
                         for a in colls.values())
        axes = {}
        for axis, a in sorted(colls.items()):
            b = float(a.get("bytes") or 0.0)
            axes[axis] = {
                "bytes": b,
                "byte_share": b / total_coll if total_coll > 0 else 0.0,
                "min_time_s": b / interconnect
                if interconnect > 0 else 0.0,
                "by_op": dict(a.get("by_op") or {}),
            }
        row: Dict[str, Any] = {
            "roofline": rl,
            "memory": dict(prog.get("memory") or {}),
            "collectives": axes,
            "collective_bytes": total_coll,
            "compiles": float(prog.get("compiles") or 0.0),
            "compile_seconds": float(prog.get("compile_seconds")
                                     or 0.0),
        }
        m = measured.get(name) or {}
        step_s = float(m.get("step_time_s") or 0.0)
        achieved = float(m.get("achieved_flops_per_sec") or 0.0)
        if not achieved and step_s > 0 and flops > 0:
            achieved = flops / step_s
        if achieved > 0:
            row["achieved_flops_per_sec"] = achieved
            row["mfu"] = achieved / peak_flops if peak_flops else 0.0
            if rl["attainable_flops_per_sec"] > 0:
                row["of_attainable"] = \
                    achieved / rl["attainable_flops_per_sec"]
        if step_s > 0:
            comm_s = sum(a["min_time_s"] for a in axes.values())
            compute_s = min(rl["min_time_s"], step_s)
            decomp = {"compute_min_s": compute_s,
                      "collective_min_s": comm_s,
                      "other_s": max(step_s - compute_s - comm_s,
                                     0.0),
                      "step_time_s": step_s}
            decomp["shares"] = {
                "compute": compute_s / step_s,
                "collective": min(comm_s / step_s, 1.0),
                "other": decomp["other_s"] / step_s,
            }
            decomp["axis_time_shares"] = {
                axis: min(a["min_time_s"] / step_s, 1.0)
                for axis, a in axes.items()}
            row["decomposition"] = decomp
        rows[name] = row
    return {
        "ts": time.time(),
        "peaks": {"device_kind": device_kind,
                  "flops_per_sec": peak_flops,
                  "hbm_bytes_per_sec": peak_hbm,
                  "interconnect_bytes_per_sec": interconnect},
        "programs": rows,
    }


def _fmt(v: float) -> str:
    for unit, div in (("T", 1e12), ("G", 1e9), ("M", 1e6),
                      ("k", 1e3)):
        if abs(v) >= div:
            return f"{v / div:.2f}{unit}"
    return f"{v:.1f}"


def render_report(report: Dict[str, Any]) -> str:
    """Human-readable `rt perf` report."""
    lines: List[str] = []
    pk = report.get("peaks") or {}
    lines.append(
        f"Peaks ({pk.get('device_kind') or '?'}): "
        f"{_fmt(pk.get('flops_per_sec', 0.0))}FLOP/s  HBM "
        f"{_fmt(pk.get('hbm_bytes_per_sec', 0.0))}B/s  ICI "
        f"{_fmt(pk.get('interconnect_bytes_per_sec', 0.0))}B/s")
    programs = report.get("programs") or {}
    if not programs:
        lines.append("(no compiled programs registered yet — run a "
                     "train step or LLM engine with telemetry on)")
    for name, row in programs.items():
        rl = row.get("roofline") or {}
        lines.append(f"\n{name}:")
        lines.append(
            f"  roofline        {_fmt(rl.get('flops', 0.0))}FLOP  "
            f"{_fmt(rl.get('bytes', 0.0))}B  intensity "
            f"{rl.get('intensity', 0.0):.1f} FLOP/B "
            f"({rl.get('bound', '?')}-bound; ridge "
            f"{rl.get('ridge_intensity', 0.0):.1f})")
        lines.append(
            f"  attainable      "
            f"{_fmt(rl.get('attainable_flops_per_sec', 0.0))}FLOP/s"
            + (f"  achieved {_fmt(row['achieved_flops_per_sec'])}"
               f"FLOP/s" if row.get("achieved_flops_per_sec") else "")
            + (f"  ({100 * row['of_attainable']:.1f}% of attainable, "
               f"MFU {100 * row.get('mfu', 0.0):.1f}%)"
               if row.get("of_attainable") else ""))
        mem = row.get("memory") or {}
        if mem:
            parts = "  ".join(f"{k}={_fmt(v)}B" for k, v in
                              sorted(mem.items()) if v)
            lines.append(f"  memory          {parts}")
        for axis, a in (row.get("collectives") or {}).items():
            ops = "  ".join(f"{op}={_fmt(b)}B" for op, b in
                            sorted(a.get("by_op", {}).items()))
            lines.append(
                f"  axis {axis:<10} {_fmt(a['bytes'])}B wire "
                f"({100 * a['byte_share']:.1f}% of collective bytes, "
                f"min {a['min_time_s'] * 1e3:.2f}ms)  {ops}")
        d = row.get("decomposition")
        if d:
            sh = d.get("shares") or {}
            lines.append(
                f"  decomposition   step {d['step_time_s'] * 1e3:.1f}"
                f"ms = compute {d['compute_min_s'] * 1e3:.1f}ms "
                f"({100 * sh.get('compute', 0.0):.0f}%) + collective "
                f"{d['collective_min_s'] * 1e3:.1f}ms "
                f"({100 * sh.get('collective', 0.0):.0f}%) + other "
                f"{d['other_s'] * 1e3:.1f}ms")
            ax = d.get("axis_time_shares") or {}
            if ax:
                lines.append("                  " + "  ".join(
                    f"{axis}={100 * s:.1f}%"
                    for axis, s in sorted(ax.items())))
        if row.get("compiles"):
            lines.append(
                f"  compiles        {row['compiles']:.0f} "
                f"({row['compile_seconds']:.2f}s total)")
    dm = report.get("device_memory") or {}
    if dm:
        lines.append("\nDevice memory:")
        for src in sorted(dm):
            for dev in sorted(dm[src]):
                row = dm[src][dev]
                limit = row.get("limit", 0.0)
                used = row.get("used", 0.0)
                peak = row.get("peak", 0.0)
                pct = f" ({100 * used / limit:.1f}% used, peak " \
                      f"{100 * peak / limit:.1f}%)" if limit else ""
                lines.append(
                    f"  {src} dev{dev}: used {_fmt(used)}B  peak "
                    f"{_fmt(peak)}B  limit {_fmt(limit)}B{pct}")
    return "\n".join(lines) + "\n"


# ==================================================================
# jax layer — everything below imports jax lazily and never raises
# into a training or request path.

_PROGRAMS: Dict[str, Dict[str, Any]] = {}
_PLOCK = threading.Lock()


def local_programs() -> Dict[str, Dict[str, Any]]:
    """This process's registered programs (deep-ish copy)."""
    with _PLOCK:
        return {k: dict(v) for k, v in _PROGRAMS.items()}


def _reset_local() -> None:
    with _PLOCK:
        _PROGRAMS.clear()


_NOTES = threading.local()


@contextlib.contextmanager
def traced_notes():
    """Collects what the code traced under it says of the program it
    builds (``note``): yields the dict, {key: facts}, that fills while a
    ``lower()`` inside the block runs the Python that makes the program."""
    before = getattr(_NOTES, "open", None)
    _NOTES.open = notes = {}
    try:
        yield notes
    finally:
        _NOTES.open = before


def note(key: str, **facts: Any) -> None:
    """A static fact of the program being traced on this thread (which
    path a loss took, by what shapes), for whoever collects them
    (``traced_notes``; ``train/train_step.py`` files them in the step's
    row); nothing where nobody does."""
    notes = getattr(_NOTES, "open", None)
    if notes is not None:
        notes[key] = facts


def harvest_compiled(compiled: Any,
                     mesh_axes: Optional[Dict[str, int]] = None
                     ) -> Dict[str, Any]:
    """Static facts of one jax ``Compiled`` executable: cost analysis,
    memory analysis, and the HLO collectives attributed to mesh axes.
    Each probe degrades independently (a backend without
    cost_analysis still yields the collectives)."""
    info: Dict[str, Any] = {"flops": 0.0, "bytes": 0.0, "memory": {},
                            "collectives": {}, "collective_counts": {},
                            "device_kind": chips.local_device_kind()}
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        info["flops"] = float(cost.get("flops", 0.0) or 0.0)
        info["bytes"] = float(cost.get("bytes accessed", 0.0) or 0.0)
    except Exception:
        pass
    try:
        mem = compiled.memory_analysis()
        for kind, attr in (("argument", "argument_size_in_bytes"),
                           ("output", "output_size_in_bytes"),
                           ("temp", "temp_size_in_bytes"),
                           ("alias", "alias_size_in_bytes"),
                           ("code", "generated_code_size_in_bytes")):
            v = getattr(mem, attr, None)
            if v:
                info["memory"][kind] = float(v)
        info["memory"]["peak"] = (
            info["memory"].get("argument", 0.0)
            + info["memory"].get("output", 0.0)
            + info["memory"].get("temp", 0.0)
            - info["memory"].get("alias", 0.0))
    except Exception:
        pass
    try:
        colls = parse_hlo_collectives(compiled.as_text())
        info["collective_counts"] = count_collectives(colls)
        info["collectives"] = summarize_collectives(colls, mesh_axes)
    except Exception:
        pass
    return info


def register_compiled(name: str, compiled: Any,
                      mesh_axes: Optional[Dict[str, int]] = None,
                      compile_seconds: Optional[float] = None,
                      notes: Optional[Dict[str, Any]] = None
                      ) -> Optional[Dict[str, Any]]:
    """Harvest one compiled program and publish its ``rt_xla_*``
    series; returns the harvested info (None on total failure).
    ``mesh_axes`` is the ORDERED {axis: size} of the mesh the program
    was compiled against (``dict(zip(mesh.axis_names,
    mesh.devices.shape))``); ``notes`` ({key: facts}, ``traced_notes``)
    join the row under their keys."""
    try:
        info = {**(notes or {}), **harvest_compiled(compiled, mesh_axes)}
        info["compiles"] = 1
        info["compile_seconds"] = float(compile_seconds or 0.0)
        with _PLOCK:
            prev = _PROGRAMS.get(name)
            if prev:
                info["compiles"] += prev.get("compiles", 0)
                info["compile_seconds"] += prev.get(
                    "compile_seconds", 0.0)
            _PROGRAMS[name] = info
        _publish_program(name, info)
        return info
    except Exception:
        return None


def _publish_program(name: str, info: Dict[str, Any]) -> None:
    from .metrics import Counter, Gauge

    tags = {"fn": name}
    # The device the program was compiled for rides this one series, so
    # a jax-free `rt perf` can pick the right row of the peak table.
    Gauge("rt_xla_cost_flops",
          "cost_analysis() flops of the registered program "
          "(per device).", tag_keys=("fn", "device_kind")).set(
        info["flops"],
        tags={"fn": name,
              "device_kind": info.get("device_kind") or ""})
    Gauge("rt_xla_cost_bytes",
          "cost_analysis() bytes accessed of the registered program "
          "(per device).", tag_keys=("fn",)).set(info["bytes"],
                                                 tags=tags)
    mem_g = Gauge("rt_xla_memory_bytes",
                  "memory_analysis() sizes of the registered program.",
                  tag_keys=("fn", "kind"))
    for kind, v in (info.get("memory") or {}).items():
        mem_g.set(v, tags={"fn": name, "kind": kind})
    coll_g = Gauge("rt_xla_collective_bytes",
                   "Per-device collective wire bytes per step, "
                   "attributed to mesh axes from HLO replica groups.",
                   tag_keys=("fn", "axis", "op"))
    for axis, a in (info.get("collectives") or {}).items():
        for op, b in (a.get("by_op") or {}).items():
            coll_g.set(b, tags={"fn": name, "axis": axis, "op": op})
    count_g = Gauge("rt_xla_collective_ops",
                    "Collectives of each kind in the registered program "
                    "(an async pair once).", tag_keys=("fn", "op"))
    for op, n in (info.get("collective_counts") or {}).items():
        count_g.set(n, tags={"fn": name, "op": op})
    Counter("rt_xla_compiles_total",
            "XLA compile events per registered function.",
            tag_keys=("fn",)).inc(tags=tags)
    Counter("rt_xla_compile_seconds_total",
            "Cumulative XLA compile seconds per function.",
            tag_keys=("fn",)).inc(info.get("compile_seconds", 0.0),
                                  tags=tags)


def publish_device_memory() -> int:
    """Poll ``device.memory_stats()`` of every local device into the
    ``rt_xla_device_memory_bytes`` gauge (used/peak/limit); returns
    the number of series written.  CPU backends report no stats —
    that's 0 series, not an error.  Reads only a backend this process
    has ALREADY started: asking for devices would start one, and a
    worker without a chip lease must never do that on its own."""
    if not chips.backend_initialized():
        return 0
    import jax

    from .metrics import Gauge

    g = Gauge("rt_xla_device_memory_bytes",
              "Device memory used/peak/limit from "
              "device.memory_stats(), polled per flush tick.",
              tag_keys=("device", "kind"))
    n = 0
    for d in jax.local_devices():
        stats = d.memory_stats()
        if not stats:
            continue
        for kind, key in (("used", "bytes_in_use"),
                          ("peak", "peak_bytes_in_use"),
                          ("limit", "bytes_limit")):
            if key in stats:
                g.set(float(stats[key]),
                      tags={"device": str(d.id), "kind": kind})
                n += 1
    return n


# ------------------------------------------------------------------
# Cluster report: telemetry summary -> merged perf report (jax-free;
# this is the `rt perf` / /api/perf / state.perf entry point).

def cluster_report(*, address: Optional[str] = None,
                   summary: Optional[Dict[str, Any]] = None
                   ) -> Dict[str, Any]:
    """Assemble the cluster-wide perf report from the telemetry
    summary's ``xla`` section plus the measured train/LLM step times
    (PR-1 goodput cadence + the step-time histograms)."""
    if summary is None:
        from .telemetry import cluster_summary

        summary = cluster_summary(address=address)
    xla = summary.get("xla") or {}
    programs = xla.get("programs") or {}

    # Measured step time: merge the per-source step-time histograms
    # (sum/count across sources); achieved FLOP/s prefers the
    # session's declared-figure gauge.
    measured: Dict[str, Dict[str, float]] = {}
    tot_sum, tot_count, achieved = 0.0, 0, 0.0
    for row in (summary.get("train") or {}).values():
        st = row.get("rt_train_step_time_seconds")
        if isinstance(st, dict):
            tot_sum += st.get("sum", 0.0)
            tot_count += st.get("count", 0)
        achieved = max(achieved,
                       row.get("rt_train_achieved_flops_per_sec",
                               0.0))
    if tot_count:
        m: Dict[str, float] = {"step_time_s": tot_sum / tot_count}
        if achieved:
            m["achieved_flops_per_sec"] = achieved
        for name in programs:
            if name.startswith("train"):
                measured[name] = m
    tpot = (summary.get("llm") or {}).get("tpot")
    if isinstance(tpot, dict) and tpot.get("count"):
        for name in programs:
            if name.startswith("llm_decode"):
                measured[name] = {"step_time_s": tpot["mean"]}

    report = build_report(programs, measured)
    report["device_memory"] = xla.get("device_memory") or {}
    report["goodput"] = summary.get("goodput") or {}
    return report
