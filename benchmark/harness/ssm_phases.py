"""What the state-space mixers' names say about a serving run (beside
phases.py, moe_phases.py and attend_phases.py, which are used as they
are): from the capture the device time per decode run of the operations
under the ``ssm.*`` scopes and under ``moe.shared`` (models/granite.py),
and per prefill run of those under ``ssm.scan``; from the engine's
counters (``stats()["state"]``) the state rows a decode run updated.  A
program without these names or counters gives every reader nothing to
read: each returns None.  (A Pallas kernel for the scan or the step would
carry no scope path and would be filed by its instruction's name, as
moe_phases.py files the grouped matmuls; there is none.)"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from . import phases, ssm_flops, trace as T

SCOPES = ("ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.step",
          "ssm.gate_norm", "ssm.out_proj")
SHARED = "moe.shared"


def _filed_under(scope_path: Optional[str]) -> Optional[str]:
    parts = phases.scope_parts(scope_path or "")
    for scope in SCOPES + (SHARED,):
        if scope in parts:
            return scope
    return None


def _fwd_runs(dev, lo, hi, spans) -> List[T.Interval]:
    """The ``jit_fwd`` runs in the window that start inside one of
    ``spans`` (moe_phases.capture's rule)."""
    return sorted((s, s + d) for name, s, d in dev.modules
                  if name.split("(", 1)[0] == "jit_fwd"
                  and lo <= s + d / 2 <= hi
                  and phases._covering(spans, s) is not None)


def capture(ctx) -> Optional[Dict[str, Any]]:
    """Device milliseconds per decode run under each ``ssm.*`` scope and
    under ``moe.shared``; per prefill run under ``ssm.scan``, by the
    bucket of the ``llm.prefill`` annotation the run starts in."""
    if "_ssm_capture" in ctx:
        return ctx["_ssm_capture"]
    ctx["_ssm_capture"] = out = _capture(ctx)
    if out:
        phases.note(ctx, "ssm_capture", out)
    return out


def _capture(ctx) -> Optional[Dict[str, Any]]:
    tr = phases.again(ctx)
    if not tr or not tr.devices:
        return None
    lo, hi = T.window_of(tr)
    dev = tr.devices[0]
    decodes = _fwd_runs(dev, lo, hi, phases._spans(tr, "llm.decode"))
    tagged = phases.annotation_tags(ctx["trace_path"], "llm.prefill")
    prefill_spans = sorted((s, e) for s, e, _ in tagged)
    bucket_of = {(s, e): str(tags.get("bucket", "?"))
                 for s, e, tags in tagged}
    prefills = _fwd_runs(dev, lo, hi, prefill_spans)
    scopes = phases.op_scopes(ctx["trace_path"])
    if not decodes or not scopes:
        return None
    ms: Dict[str, float] = {}
    scan_of_run: Dict[T.Interval, float] = {}
    for name, s, e in T._leaves(dev, lo, hi):
        scope = _filed_under(scopes.get(name))
        if scope is None:
            continue
        if phases._covering(decodes, s) is not None:
            ms[scope] = ms.get(scope, 0.0) + (e - s) / 1e6
        elif scope == "ssm.scan":
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
            "shared_ms": per_run.get(SHARED),
            "prefill_runs": len(scans),
            "scan_ms": sum(scans) / len(scans) if scans else None,
            "scan_ms_by_bucket": {b: sum(v) / len(v)
                                  for b, v in sorted(by_bucket.items())}}


def state_rows(ctx) -> Optional[Dict[str, float]]:
    """Per decode run, from the deltas of ``stats()["state"]`` over the
    window: state rows updated, with the bytes of one row and of one
    layer's mixer matrices."""
    serve = ctx.get("serve") or {}
    a = (serve.get("before") or {}).get("state")
    b = (serve.get("at_end") or {}).get("state")
    if not a or not b or b["decode_runs"] <= a["decode_runs"]:
        return None
    runs = b["decode_runs"] - a["decode_runs"]
    out = {"runs": runs,
           "state_rows_updated": (b["state_rows_updated"]
                                  - a["state_rows_updated"]) / runs,
           "state_row_bytes": b["state_row_bytes"],
           "mixer_weight_bytes": b["mixer_weight_bytes"],
           "slots_used_at_end": b["slots_used"],
           "slots_total": b["slots_total"]}
    phases.note(ctx, "ssm_state_rows_per_run", out)
    return out


def mixer_roofline(ctx) -> Optional[Dict[str, Any]]:
    """The least time the chip's memory could take for what one decode
    run's mixers must move (ssm_flops.decode_mixer_bytes), over the time
    they took."""
    cap, r = capture(ctx), state_rows(ctx)
    if not cap or not r or not cap["mixer_ms"]:
        return None
    nbytes = ssm_flops.decode_mixer_bytes(
        r["state_rows_updated"], r["state_row_bytes"],
        r["mixer_weight_bytes"], ctx["sizes"]["ssm_layers"])
    least = ssm_flops.least_ms(nbytes, ctx["peaks"].hbm_bytes_per_s)
    out = {"pct": 100.0 * least / cap["mixer_ms"], "bytes": nbytes,
           "least_ms": least}
    phases.note(ctx, "ssm_mixer_roofline", out)
    return out
