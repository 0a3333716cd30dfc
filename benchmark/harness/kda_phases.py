"""What the delta-rule (KDA) mixers' names say about a serving run (beside
phases.py, moe_phases.py, attend_phases.py, ssm_phases.py, conv_phases.py
and mla_phases.py, which are used as they are): from the capture the device
time per decode run of the operations under the ``kda.*`` scopes
(models/kimi_linear.py) and under ``moe.shared`` / ``mlp.dense``, and per
prefill run of those under ``kda.scan``; from the engine's counters
(``stats()["state"]``, read by ssm_phases.state_rows) the slots a decode
run moved (``slots_total``: the step works by slot over the whole slab)
beside the running rows (``state_rows_updated``).  A Pallas kernel named
``kda_step`` would carry no scope path and is filed by its instruction's
name (there is none today: the step is ``jnp``).  A program without these names or counters gives every reader
nothing to read: each returns None.

There is no share of a roofline for the WHOLE mixer (conv_phases.py has the
readings that say why: the compiler prefetches such weights under other
layers' operations); ``mixer_floor`` puts the bytes and their least time
beside ``kda.mixer_ms.sat`` in the info line.  The recurrence's state
cannot be prefetched so, and ``step_roofline`` holds it against the time
under ``kda.step``, with any asynchronous copy of a state-shaped array
(``f32[.., heads, d_k, d_v]``: no scope of its own) added to that time."""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional

from . import kda_flops, phases, ssm_phases, trace as T

SCOPES = ("kda.proj", "kda.conv", "kda.gate", "kda.step", "kda.scan",
          "kda.out_norm", "kda.out_proj")
OTHER = ("moe.shared", "mlp.dense")


def _filed_under(op_name: str, scope_path: Optional[str]) -> Optional[str]:
    if T.op_label(op_name).startswith("kda_step"):
        return "kda.step"
    parts = phases.scope_parts(scope_path or "")
    for scope in SCOPES + OTHER:
        if scope in parts:
            return scope
    return None


def capture(ctx) -> Optional[Dict[str, Any]]:
    """Device milliseconds per decode run under each ``kda.*`` scope and
    under ``moe.shared`` / ``mlp.dense``; per prefill run under
    ``kda.scan``, by the bucket of the ``llm.prefill`` annotation the run
    starts in."""
    if "_kda_capture" in ctx:
        return ctx["_kda_capture"]
    ctx["_kda_capture"] = out = _capture(ctx)
    if out:
        phases.note(ctx, "kda_capture", out)
    return out


def _capture(ctx) -> Optional[Dict[str, Any]]:
    tr = phases.again(ctx)
    sizes = ctx.get("sizes") or {}
    if not tr or not tr.devices or "kda_heads" not in sizes:
        return None
    lo, hi = T.window_of(tr)
    dev = tr.devices[0]
    decodes = ssm_phases._fwd_runs(dev, lo, hi,
                                   phases._spans(tr, "llm.decode"))
    tagged = phases.annotation_tags(ctx["trace_path"], "llm.prefill")
    prefill_spans = sorted((s, e) for s, e, _ in tagged)
    bucket_of = {(s, e): str(tags.get("bucket", "?"))
                 for s, e, tags in tagged}
    prefills = ssm_phases._fwd_runs(dev, lo, hi, prefill_spans)
    scopes = phases.op_scopes(ctx["trace_path"])
    if not decodes or not scopes:
        return None
    state_shaped = re.compile(r"f32\[[\d,]*{},{},{}\]".format(
        sizes["kda_heads"], sizes["kda_head_dim"], sizes["kda_head_dim"]))
    ms: Dict[str, float] = {}
    state_copy_ms = 0.0
    scan_of_run: Dict[T.Interval, float] = {}
    for name, s, e in T._leaves(dev, lo, hi):
        scope = _filed_under(name, scopes.get(name))
        in_decode = phases._covering(decodes, s) is not None
        if scope is None:
            if in_decode and T.opcode(name).endswith(("-start", "-done")) \
                    and state_shaped.search(name):
                state_copy_ms += (e - s) / 1e6
            continue
        if in_decode:
            ms[scope] = ms.get(scope, 0.0) + (e - s) / 1e6
        elif scope == "kda.scan":
            i = phases._covering(prefills, s)
            if i is not None:
                scan_of_run[prefills[i]] = scan_of_run.get(
                    prefills[i], 0.0) + (e - s) / 1e6
    if not any(k in ms for k in SCOPES):
        return None
    per_run = {k: v / len(decodes) for k, v in ms.items()}
    by_bucket: Dict[str, List[float]] = {}
    for run, scan_ms in scan_of_run.items():
        i = phases._covering(prefill_spans, run[0])
        by_bucket.setdefault(bucket_of[prefill_spans[i]], []).append(scan_ms)
    scans = [v for runs in by_bucket.values() for v in runs]
    return {"decode_runs": len(decodes), "ms_by_scope": per_run,
            "mixer_ms": sum(per_run.get(k, 0.0) for k in SCOPES),
            "step_ms": per_run.get("kda.step"),
            "state_copy_ms": state_copy_ms / len(decodes),
            "shared_ms": per_run.get("moe.shared"),
            "dense_ms": per_run.get("mlp.dense"),
            "prefill_runs": len(scans),
            "scan_ms": sum(scans) / len(scans) if scans else None,
            "scan_ms_by_bucket": {b: v for b, v in sorted(by_bucket.items())}}


def _rows_moved(r, sizes) -> float:
    """State rows one decode run READS AND WRITES: ``kda_step`` and
    ``step_conv`` work by slot over the layer's whole slab, so every slot of
    the pool moves in every KDA layer, whether its sequence is in the step
    or not (``state_rows_updated`` counts the running rows alone: what the
    mathematics needs; the two agree where the batch is full)."""
    return float(r["slots_total"] * sizes["kda_layers"])


def mixer_floor(ctx) -> Optional[Dict[str, Any]]:
    """For the info line, beside the mixers' scoped time: what one decode
    run's mixers move (kda_flops.decode_mixer_bytes over every slot of the
    pool) and the least time the chip's memory could take for it.  Not a
    share of a roofline: the module docstring has the why."""
    cap, r = capture(ctx), ssm_phases.state_rows(ctx)
    if not cap or not r:
        return None
    nbytes = kda_flops.decode_mixer_bytes(
        _rows_moved(r, ctx["sizes"]), r["state_row_bytes"],
        r["mixer_weight_bytes"], ctx["sizes"]["kda_layers"])
    out = {"bytes": nbytes, "scoped_ms": cap["mixer_ms"],
           "rows_moved": _rows_moved(r, ctx["sizes"]),
           "rows_running": r["state_rows_updated"],
           "least_ms": kda_flops.least_ms(
               nbytes, ctx["peaks"].hbm_bytes_per_s)}
    phases.note(ctx, "kda_mixer_floor", out)
    return out


def step_roofline(ctx) -> Optional[Dict[str, Any]]:
    """The least time the chip's memory could take to read and write the
    states one decode run's recurrence MOVES (every slot of the pool a
    layer: ``_rows_moved``), over the time under ``kda.step`` (with the
    state-shaped asynchronous copies)."""
    cap, r = capture(ctx), ssm_phases.state_rows(ctx)
    if not cap or not r or not cap["step_ms"]:
        return None
    s = ctx["sizes"]
    nbytes = kda_flops.step_bytes(_rows_moved(r, s), s["kda_heads"],
                                  s["kda_head_dim"])
    least = kda_flops.least_ms(nbytes, ctx["peaks"].hbm_bytes_per_s)
    took = cap["step_ms"] + cap["state_copy_ms"]
    out = {"pct": 100.0 * least / took, "bytes": nbytes,
           "rows_moved": _rows_moved(r, s),
           "rows_running": r["state_rows_updated"],
           "least_ms": least, "took_ms": took}
    phases.note(ctx, "kda_step_roofline", out)
    return out


def scan_roofline(ctx) -> Optional[Dict[str, Any]]:
    """Over the capture's prefill runs: the least time the chip could take
    for what the delta rule's mathematics needs at each run's BUCKET's
    length (kda_flops.scan_flops / scan_bytes), summed, over the time under
    ``kda.scan``, summed."""
    cap = capture(ctx)
    if not cap or not cap["scan_ms_by_bucket"]:
        return None
    s = ctx["sizes"]
    shape = dict(layers=s["kda_layers"], heads=s["kda_heads"],
                 head_dim=s["kda_head_dim"])
    least = took = 0.0
    for bucket, runs in cap["scan_ms_by_bucket"].items():
        t = int(bucket)
        compute = kda_flops.scan_flops(t, **shape) / ctx["peaks"].flops_per_s
        memory = kda_flops.scan_bytes(t, **shape) \
            / ctx["peaks"].hbm_bytes_per_s
        least += len(runs) * max(compute, memory)
        took += sum(runs) / 1e3
    out = {"pct": 100.0 * least / took,
           "bound": "compute" if compute >= memory else "memory",
           "least_s": least, "took_s": took}
    phases.note(ctx, "kda_scan_roofline", out)
    return out
