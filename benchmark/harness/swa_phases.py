"""What the two kinds of attention of a model with sliding-window layers say
about a serving run (beside gdn_phases.py and attend_phases.py, which are
used as they are): from the capture the device time per PREFILL run of the
operations under the scope ``attn.window`` (a window layer's attention core:
the flash kernel under the band and what surrounds it) and under
``attn.full`` (a full layer's: the causal triangle), by the bucket of the
``llm.prefill`` annotation the run starts in, and per DECODE run of the
window layers' attend (``kv.attend`` under ``attn.window``: the
paged-decode kernel over the rings); the rows' store, ``kv.store``, lies
inside both scopes and is filed apart.  Only runs that lie WHOLLY inside
the capture's window are read.  From the engine's counters
(``stats()["attention"]``) the ring rows a decode run read and what the two
groups hold.

A kernel's event may carry no scope path (a custom call's metadata): a
``flash_fwd`` or ``paged_decode`` event without one is filed by its ORDER
in its run, the i-th call of a run belonging to layer i of the
configuration's ``layer_types``.

A program without these scopes or counters (a parent of the PR that brought
the family, any other family) gives every reader nothing to read: each
returns None."""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from . import phases, ssm_phases, swa_flops, trace as T

WINDOW, FULL = "attn.window", "attn.full"
KERNELS = ("flash_fwd", "paged_decode")
KIND_SCOPE = {"sliding_attention": WINDOW, "full_attention": FULL}


def _kernel(op_name: str) -> Optional[str]:
    label = T.op_label(op_name)
    return next((k for k in KERNELS if label.startswith(k)), None)


def _scope_of(parts: List[str]) -> Optional[str]:
    return next((s for s in (WINDOW, FULL) if s in parts), None)


def file_run(ops, scopes: Dict[str, str], kinds: List[str]
             ) -> Dict[str, float]:
    """Device ms of one run's operations ``(name, start, end)`` by where
    they are filed: ``attn.window`` / ``attn.full`` (``kv.store`` apart, as
    ``<scope>/kv.store``; the attend of a decode step as
    ``<scope>/kv.attend``).  A kernel event with no scope path is filed by
    its order among its kernel's events in the run."""
    out: Dict[str, float] = {}
    seen = {k: 0 for k in KERNELS}
    for name, s, e in sorted(ops, key=lambda op: op[1]):
        parts = phases.scope_parts(scopes.get(name) or "")
        scope, kernel = _scope_of(parts), _kernel(name)
        if kernel is not None:
            i, seen[kernel] = seen[kernel], seen[kernel] + 1
            if scope is None and i < len(kinds):
                scope = KIND_SCOPE.get(kinds[i])
        if scope is None:
            continue
        if "kv.store" in parts:
            scope += "/kv.store"
        elif kernel == "paged_decode" or "kv.attend" in parts:
            scope += "/kv.attend"
        out[scope] = out.get(scope, 0.0) + (e - s) / 1e6
    return out


def capture(ctx) -> Optional[Dict[str, Any]]:
    if "_swa_capture" in ctx:
        return ctx["_swa_capture"]
    ctx["_swa_capture"] = out = _capture(ctx)
    if out:
        phases.note(ctx, "swa_capture", out)
    return out


def _capture(ctx) -> Optional[Dict[str, Any]]:
    tr = phases.again(ctx)
    sizes = ctx.get("sizes") or {}
    if not tr or not tr.devices or not sizes.get("window_layers"):
        return None
    kinds = list(getattr(ctx.get("cell"), "config", {}).get(
        "layer_types", ()))
    lo, hi = T.window_of(tr)
    dev = tr.devices[0]
    decodes = ssm_phases._fwd_runs(dev, lo, hi,
                                   phases._spans(tr, "llm.decode"))
    tagged = phases.annotation_tags(ctx["trace_path"], "llm.prefill")
    prefill_spans = sorted((s, e) for s, e, _ in tagged)
    bucket_of = {(s, e): str(tags.get("bucket", "?"))
                 for s, e, tags in tagged}
    prefills = ssm_phases._fwd_runs(dev, lo, hi, prefill_spans)
    # WHOLE runs only: a run the capture's edge cuts would give a part of
    # its time against all of its work, and read over its roofline
    decodes, prefills = ([run for run in runs if lo <= run[0]
                          and run[1] <= hi] for runs in (decodes, prefills))
    scopes = phases.op_scopes(ctx["trace_path"])
    if not scopes or not (decodes or prefills):
        return None
    by_run: Dict[T.Interval, list] = {}
    for name, s, e in T._leaves(dev, lo, hi):
        for runs in (decodes, prefills):
            i = phases._covering(runs, s)
            if i is not None:
                by_run.setdefault(runs[i], []).append((name, s, e))
    decode_ms: Dict[str, float] = {}
    for run in decodes:
        for key, ms in file_run(by_run.get(run, ()), scopes, kinds).items():
            decode_ms[key] = decode_ms.get(key, 0.0) + ms
    window: Dict[str, List[float]] = {}
    full: Dict[str, List[float]] = {}
    for run in prefills:
        filed = file_run(by_run.get(run, ()), scopes, kinds)
        i = phases._covering(prefill_spans, run[0])
        bucket = bucket_of[prefill_spans[i]]
        for scope, into in ((WINDOW, window), (FULL, full)):
            if scope in filed:
                into.setdefault(bucket, []).append(filed[scope])
    if not decode_ms and not window and not full:
        return None

    def mean(buckets) -> Optional[float]:
        values = [v for runs in buckets.values() for v in runs]
        return sum(values) / len(values) if values else None

    n = len(decodes)
    return {"decode_runs": n, "prefill_runs": len(prefills),
            "decode_ms": {k: v / n for k, v in sorted(decode_ms.items())},
            "attend_ms": decode_ms[WINDOW + "/kv.attend"] / n
            if WINDOW + "/kv.attend" in decode_ms else None,
            "window_ms": mean(window),
            "window_ms_by_bucket": dict(sorted(window.items())),
            "full_ms": mean(full),
            "full_ms_by_bucket": dict(sorted(full.items()))}


def _over_buckets(ctx, key: str, need) -> Optional[Dict[str, Any]]:
    """Over the capture's prefill runs: the least time the chip could take
    at each run's BUCKET's length (``need(t)`` -> (FLOPs, bytes)), summed,
    over the time the runs took under ``key``, summed."""
    cap = capture(ctx)
    if not cap or not cap[key]:
        return None
    peaks = ctx["peaks"]
    least = took = 0.0
    for bucket, runs in cap[key].items():
        least += len(runs) * swa_flops.least_ms(
            *need(int(bucket)), peaks.flops_per_s, peaks.hbm_bytes_per_s)
        took += sum(runs)
    return {"pct": 100.0 * least / took, "least_ms": least, "took_ms": took}


def prefill_roofline(ctx) -> Optional[Dict[str, Any]]:
    """The window layers' band of a prefill against the chip."""
    s = ctx.get("sizes") or {}
    if not s.get("window_layers"):
        return None
    shape = (s["window_layers"], s["n_head"], s["head_dim"])
    out = _over_buckets(ctx, "window_ms_by_bucket", lambda t: (
        swa_flops.band_flops(t, *shape, s["window"]),
        swa_flops.prefill_bytes(t, s["window_layers"], s["n_head"],
                                s["n_kv_head"], s["head_dim"])))
    if out:
        phases.note(ctx, "swa_prefill_roofline", out)
    return out


def full_prefill_roofline(ctx) -> Optional[Dict[str, Any]]:
    """The full layers' causal triangle of a prefill against the chip."""
    s = ctx.get("sizes") or {}
    if not s.get("window_layers"):
        return None
    shape = (s["kv_layers"], s["n_head"], s["head_dim"])
    out = _over_buckets(ctx, "full_ms_by_bucket", lambda t: (
        swa_flops.triangle_flops(t, *shape),
        swa_flops.prefill_bytes(t, s["kv_layers"], s["n_head"],
                                s["n_kv_head"], s["head_dim"])))
    if out:
        phases.note(ctx, "swa_full_prefill_roofline", out)
    return out


def rows(ctx) -> Optional[Dict[str, float]]:
    """Per decode run, from the deltas of ``stats()["attention"]`` over the
    window: the window group's rows read, held and dropped, both groups'
    rows held, and the bytes of one row."""
    serve = ctx.get("serve") or {}
    a = (serve.get("before") or {}).get("attention")
    b = (serve.get("at_end") or {}).get("attention")
    if not a or not b or "window_rows_read" not in b \
            or b["decode_runs"] <= a["decode_runs"]:
        return None
    runs = b["decode_runs"] - a["decode_runs"]
    out = {key: (b[key] - a[key]) / runs for key in (
        "window_rows_read", "window_rows_held", "window_positions_dropped",
        "kv_rows_read", "kv_rows_held")}
    out.update(runs=runs, kv_row_bytes=b["kv_row_bytes"],
               window=b["window"], window_layers=b["window_layers"])
    phases.note(ctx, "swa_rows_per_run", out)
    return out


def attend_roofline(ctx) -> Optional[Dict[str, Any]]:
    """The least time the chip's memory could take to read the ring rows
    one decode run's window layers read, over the time their attend took."""
    cap, r = capture(ctx), rows(ctx)
    if not cap or not r or not cap["attend_ms"]:
        return None
    nbytes = swa_flops.attend_bytes(r["window_rows_read"], r["kv_row_bytes"])
    least = 1e3 * nbytes / ctx["peaks"].hbm_bytes_per_s
    out = {"pct": 100.0 * least / cap["attend_ms"], "bytes": nbytes,
           "least_ms": least, "took_ms": cap["attend_ms"]}
    phases.note(ctx, "swa_attend_roofline", out)
    return out


def kv_held_share(ctx) -> Optional[float]:
    """Positions the two groups hold over what one group that kept every
    position in every layer would, in percent: from the engine's counters,
    the full group's rows a run held spread over its layers give a layer's
    positions, which every window layer would hold too."""
    r, s = rows(ctx), ctx.get("sizes") or {}
    if not r or not s.get("kv_layers"):
        return None
    layer = (r["kv_rows_held"] - r["window_rows_held"]) / s["kv_layers"]
    blind = layer * (s["kv_layers"] + r["window_layers"])
    return 100.0 * r["kv_rows_held"] / blind if blind else None
