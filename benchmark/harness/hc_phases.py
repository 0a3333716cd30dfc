"""What the residual path's names say about a serving run (beside phases.py,
mla_phases.py, moe_phases.py, ..., which are used as they are): from the
capture the device time per DECODE run of the operations under ``hc.map``
(models/xing.py: the flattened norm, ``r Phi``, the sigmoids and the
Sinkhorn) and under ``hc.pre`` + ``hc.post`` (the two mixes: a sublayer's
input read from the streams, its output written back), and per PREFILL run,
by the bucket of the ``llm.prefill`` annotation the run starts in, of those
under all three.  The entry and the exit (``hc.begin``, ``hc.end``: one copy
and one sum a program) are noted in the info line and are in no metric.  A
program without these names (every family of ONE stream, and a parent of the
PR that brought them) gives every reader nothing to read: each returns
None."""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from . import hc_flops, phases, ssm_phases, trace as T

SCOPES = ("hc.map", "hc.pre", "hc.post")
NOTED = ("hc.begin", "hc.end")


def _filed_under(scope_path: Optional[str]) -> Optional[str]:
    parts = phases.scope_parts(scope_path or "")
    for scope in SCOPES + NOTED:
        if scope in parts:
            return scope
    return None


def capture(ctx) -> Optional[Dict[str, Any]]:
    if "_hc_capture" in ctx:
        return ctx["_hc_capture"]
    ctx["_hc_capture"] = out = _capture(ctx)
    if out:
        phases.note(ctx, "hc_capture", out)
    return out


def _capture(ctx) -> Optional[Dict[str, Any]]:
    tr = phases.again(ctx)
    if not tr or not tr.devices:
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
    if not scopes or not (decodes or prefills):
        return None
    decode_ms: Dict[str, float] = {}
    prefill_ms: Dict[str, float] = {}
    of_run: Dict[T.Interval, float] = {}
    for name, s, e in T._leaves(dev, lo, hi):
        scope = _filed_under(scopes.get(name))
        if scope is None:
            continue
        ms = (e - s) / 1e6
        if phases._covering(decodes, s) is not None:
            decode_ms[scope] = decode_ms.get(scope, 0.0) + ms
            continue
        i = phases._covering(prefills, s)
        if i is not None:
            prefill_ms[scope] = prefill_ms.get(scope, 0.0) + ms
            if scope in SCOPES:
                of_run[prefills[i]] = of_run.get(prefills[i], 0.0) + ms
    if not any(k in SCOPES for k in (*decode_ms, *prefill_ms)):
        return None
    by_bucket: Dict[str, List[float]] = {}
    for run, ms in of_run.items():
        i = phases._covering(prefill_spans, run[0])
        by_bucket.setdefault(bucket_of[prefill_spans[i]], []).append(ms)
    runs = [v for bucket in by_bucket.values() for v in bucket]
    n_d, n_p = max(len(decodes), 1), max(len(prefills), 1)
    per_decode = {k: v / n_d for k, v in decode_ms.items()}
    return {
        "decode_runs": len(decodes), "decode_ms_by_scope": per_decode,
        "map_ms": per_decode.get("hc.map") if decodes else None,
        "mix_ms": per_decode.get("hc.pre", 0.0)
        + per_decode.get("hc.post", 0.0) if decodes else None,
        "prefill_runs": len(prefills),
        "prefill_ms_by_scope": {k: v / n_p for k, v in prefill_ms.items()},
        "prefill_ms": sum(runs) / len(runs) if runs else None,
        "prefill_ms_by_bucket": dict(sorted(by_bucket.items())),
        # the engine's own counters at the window's end (streams,
        # sublayers, the Sinkhorn's rounds, how far the last program's
        # stream maps lay from doubly stochastic)
        "stats_residual": ((ctx.get("serve") or {}).get("at_end")
                           or {}).get("residual")}


def prefill_roofline(ctx) -> Optional[Dict[str, Any]]:
    """Over the capture's prefill runs: the least time the chip could take
    for each run's steps 1-4 at its BUCKET's length (hc_flops), summed, over
    the time they took, summed."""
    cap = capture(ctx)
    s = ctx["sizes"]
    if not cap or not cap["prefill_ms_by_bucket"] or "hc_mult" not in s:
        return None
    shape = dict(sublayers=s["hc_sublayers"], n=s["hc_mult"],
                 d=s["d_model"])
    iters = ctx["cell"].config["hc_sinkhorn_iters"]
    least = took = 0.0
    for bucket, runs in cap["prefill_ms_by_bucket"].items():
        t = int(bucket)
        least += len(runs) * max(
            hc_flops.hc_flops(t, iters=iters, **shape)
            / ctx["peaks"].flops_per_s,
            hc_flops.hc_bytes(t, **shape) / ctx["peaks"].hbm_bytes_per_s)
        took += sum(runs) / 1e3
    out = {"pct": 100.0 * least / took, "least_s": least, "took_s": took}
    phases.note(ctx, "hc_prefill_roofline", out)
    return out
