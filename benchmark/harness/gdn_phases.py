"""What the Gated DeltaNet mixers' and the K/V prefill's names say about a
serving run (beside kda_phases.py, whose readers are Kimi-Linear's: square
states, every slot of the pool moved): from the capture the device time per
decode run of the operations under the ``gdn.*`` scopes
(models/olmo_hybrid.py), the step kernel filed by its instruction's name
(``kda_step``: a kernel carries no scope path) under ``gdn.step``; per
prefill run of those under ``gdn.scan`` and of the K/V layers' ``attn.core``
(the flash kernel ``flash_fwd``, filed by name, with what surrounds it; the
rows' store, ``kv.store``, is not in it); from the engine's counters
(``stats()["state"]``, read by ssm_phases.state_rows) the rows a decode run
updated.  A program without these names or counters (a parent of the PR
that brought the family, any other family) gives every reader nothing to
read: each returns None."""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from . import gdn_flops, phases, ssm_phases, trace as T

SCOPES = ("gdn.proj", "gdn.conv", "gdn.gate", "gdn.step", "gdn.scan",
          "gdn.out_norm", "gdn.out_proj")
# ``kv.store`` lies inside ``attn.core``: named first, it is filed apart
ATTEND = ("kv.store", "kv.attend", "attn.core")


def _filed_under(op_name: str, scope_path: Optional[str]) -> Optional[str]:
    label = T.op_label(op_name)
    if label.startswith("kda_step"):
        return "gdn.step"
    if label.startswith("flash_fwd"):
        return "attn.core"
    parts = phases.scope_parts(scope_path or "")
    for scope in SCOPES + ATTEND:
        if scope in parts:
            return scope
    return None


def capture(ctx) -> Optional[Dict[str, Any]]:
    """Device milliseconds per decode run under each ``gdn.*`` scope; per
    prefill run under ``gdn.scan`` and under ``attn.core``, by the bucket of
    the ``llm.prefill`` annotation the run starts in."""
    if "_gdn_capture" in ctx:
        return ctx["_gdn_capture"]
    ctx["_gdn_capture"] = out = _capture(ctx)
    if out:
        phases.note(ctx, "gdn_capture", out)
    return out


def _capture(ctx) -> Optional[Dict[str, Any]]:
    tr = phases.again(ctx)
    sizes = ctx.get("sizes") or {}
    if not tr or not tr.devices or "gdn_heads" not in sizes:
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
    of_run: Dict[str, Dict[T.Interval, float]] = {"gdn.scan": {},
                                                  "attn.core": {}}
    for name, s, e in T._leaves(dev, lo, hi):
        scope = _filed_under(name, scopes.get(name))
        if scope is None:
            continue
        ms = (e - s) / 1e6
        if phases._covering(decodes, s) is not None:
            decode_ms[scope] = decode_ms.get(scope, 0.0) + ms
        elif scope in of_run:
            i = phases._covering(prefills, s)
            if i is not None:
                runs = of_run[scope]
                runs[prefills[i]] = runs.get(prefills[i], 0.0) + ms
    if not any(k in decode_ms for k in SCOPES) and not of_run["gdn.scan"]:
        return None

    def by_bucket(runs) -> Dict[str, List[float]]:
        out: Dict[str, List[float]] = {}
        for run, ms in runs.items():
            i = phases._covering(prefill_spans, run[0])
            out.setdefault(bucket_of[prefill_spans[i]], []).append(ms)
        return dict(sorted(out.items()))

    def mean(buckets) -> Optional[float]:
        values = [v for runs in buckets.values() for v in runs]
        return sum(values) / len(values) if values else None

    per_run = {k: v / len(decodes) for k, v in decode_ms.items()}
    scan, attend = by_bucket(of_run["gdn.scan"]), by_bucket(
        of_run["attn.core"])
    return {"decode_runs": len(decodes), "ms_by_scope": per_run,
            "mixer_ms": sum(per_run.get(k, 0.0) for k in SCOPES)
            if decodes else None,
            "step_ms": per_run.get("gdn.step"),
            "prefill_runs": len(prefills),
            "scan_ms": mean(scan), "scan_ms_by_bucket": scan,
            "attend_ms": mean(attend), "attend_ms_by_bucket": attend}


def step_roofline(ctx) -> Optional[Dict[str, Any]]:
    """The least time the chip's memory could take to read and write the
    UNPADDED states of the rows one decode run's recurrence updated (the
    running rows alone: the kernel touches no other slot), over the time
    under ``gdn.step``."""
    cap, r = capture(ctx), ssm_phases.state_rows(ctx)
    if not cap or not r or not cap["step_ms"]:
        return None
    s = ctx["sizes"]
    nbytes = gdn_flops.step_bytes(r["state_rows_updated"], s["gdn_heads"],
                                  s["gdn_key_dim"], s["gdn_value_dim"])
    least = gdn_flops.least_ms(nbytes, ctx["peaks"].hbm_bytes_per_s)
    out = {"pct": 100.0 * least / cap["step_ms"], "bytes": nbytes,
           "rows_running": r["state_rows_updated"],
           "least_ms": least, "took_ms": cap["step_ms"]}
    phases.note(ctx, "gdn_step_roofline", out)
    return out


def _over_buckets(ctx, key: str, need) -> Optional[Dict[str, Any]]:
    """Over the capture's prefill runs: the least time the chip could take
    at each run's BUCKET's length (``need(t)`` -> (FLOPs, bytes)), summed,
    over the time the runs took under ``key``, summed."""
    cap = capture(ctx)
    if not cap or not cap[key]:
        return None
    least = took = 0.0
    for bucket, runs in cap[key].items():
        flops, nbytes = need(int(bucket))
        least += len(runs) * max(flops / ctx["peaks"].flops_per_s,
                                 nbytes / ctx["peaks"].hbm_bytes_per_s)
        took += sum(runs) / 1e3
    return {"pct": 100.0 * least / took, "least_s": least, "took_s": took}


def scan_roofline(ctx) -> Optional[Dict[str, Any]]:
    s = ctx.get("sizes") or {}
    if "gdn_heads" not in s:
        return None
    shape = dict(layers=s["gdn_layers"], heads=s["gdn_heads"],
                 d_k=s["gdn_key_dim"], d_v=s["gdn_value_dim"])
    out = _over_buckets(ctx, "scan_ms_by_bucket", lambda t: (
        gdn_flops.scan_flops(t, **shape), gdn_flops.scan_bytes(t, **shape)))
    if out:
        phases.note(ctx, "gdn_scan_roofline", out)
    return out


def prefill_attend_roofline(ctx) -> Optional[Dict[str, Any]]:
    s = ctx.get("sizes") or {}
    if "gdn_heads" not in s:
        return None
    shape = dict(layers=s["kv_layers"], heads=s["n_head"],
                 head_dim=s["head_dim"])
    out = _over_buckets(ctx, "attend_ms_by_bucket", lambda t: (
        gdn_flops.prefill_attend_flops(t, **shape),
        gdn_flops.prefill_attend_bytes(t, **shape)))
    if out:
        phases.note(ctx, "gdn_prefill_attend_roofline", out)
    return out
