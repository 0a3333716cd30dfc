"""What latent attention's names say about a serving run (beside phases.py,
moe_phases.py, attend_phases.py, ssm_phases.py and conv_phases.py, which
are used as they are): from the capture the device time per DECODE run of
the operations under ``mla.q``, ``mla.kv``, ``mla.absorb`` and ``attn.out``
(models/kimi.py, models/attention.py latent_attention: the five
projections of the absorbed path), and per PREFILL run, by the bucket of
the ``llm.prefill`` annotation the run starts in, of those under
``mla.expand`` and the rest of ``attn.core`` (the expansion through
``W_kvb`` and the causal attention among the prompt's rows: the flash
kernel, filed by its instruction's name as everywhere, and the transposes
and the padding around it).  The store of the latent rows (``kv.store``)
and the decode step's ``kv.attend`` are NOT in these: the accepted
``decode.attend_ms.sat`` has the second.  A program without these names
gives every reader nothing to read: each returns None.

``moe.shared`` and ``mlp.dense`` per decode run are noted in the info line
(``mla_capture``): the accepted readers of those names want ``ssm.*`` /
``conv.*`` scopes beside them and return nothing here."""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from . import attend_phases, flops, mla_flops, phases, ssm_phases, trace as T

PROJ = ("mla.q", "mla.kv", "mla.absorb", "attn.out")
# innermost first: ``mla.absorb``, ``mla.expand``, ``kv.store`` and
# ``kv.attend`` all lie inside ``attn.core``
ORDER = ("kv.store", "kv.attend", "mla.absorb", "mla.expand", "attn.core",
         "mla.q", "mla.kv", "attn.out", "moe.shared", "mlp.dense")
PREFILL_ATTEND = ("mla.expand", "attn.core")


def _filed_under(op_name: str, scope_path: Optional[str]) -> Optional[str]:
    label = T.op_label(op_name)
    if label.startswith("flash_fwd"):
        return "attn.core"
    if label.startswith("paged_decode"):
        return "kv.attend"
    parts = phases.scope_parts(scope_path or "")
    for scope in ORDER:
        if scope in parts:
            return scope
    return None


def capture(ctx) -> Optional[Dict[str, Any]]:
    if "_mla_capture" in ctx:
        return ctx["_mla_capture"]
    ctx["_mla_capture"] = out = _capture(ctx)
    if out:
        phases.note(ctx, "mla_capture", out)
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
    attend_of_run: Dict[T.Interval, float] = {}
    prefill_ms: Dict[str, float] = {}
    for name, s, e in T._leaves(dev, lo, hi):
        scope = _filed_under(name, scopes.get(name))
        if scope is None:
            continue
        ms = (e - s) / 1e6
        if phases._covering(decodes, s) is not None:
            decode_ms[scope] = decode_ms.get(scope, 0.0) + ms
            continue
        i = phases._covering(prefills, s)
        if i is not None:
            prefill_ms[scope] = prefill_ms.get(scope, 0.0) + ms
            if scope in PREFILL_ATTEND:
                attend_of_run[prefills[i]] = attend_of_run.get(
                    prefills[i], 0.0) + ms
    if not any(k.startswith("mla.") for k in (*decode_ms, *prefill_ms)):
        return None
    by_bucket: Dict[str, List[float]] = {}
    for run, ms in attend_of_run.items():
        i = phases._covering(prefill_spans, run[0])
        by_bucket.setdefault(bucket_of[prefill_spans[i]], []).append(ms)
    attends = [v for runs in by_bucket.values() for v in runs]
    n_d, n_p = max(len(decodes), 1), max(len(prefills), 1)
    per_decode = {k: v / n_d for k, v in decode_ms.items()}
    return {
        "decode_runs": len(decodes), "decode_ms_by_scope": per_decode,
        "proj_ms": sum(per_decode.get(k, 0.0) for k in PROJ)
        if decodes else None,
        "shared_ms": per_decode.get("moe.shared"),
        "dense_ms": per_decode.get("mlp.dense"),
        "prefill_runs": len(prefills),
        "prefill_ms_by_scope": {k: v / n_p for k, v in prefill_ms.items()},
        "prefill_attend_ms": sum(attends) / len(attends)
        if attends else None,
        "prefill_attend_ms_by_bucket": {
            b: v for b, v in sorted(by_bucket.items())}}


def _widths(ctx):
    s = ctx["sizes"]
    return dict(heads=s["n_head"], latent=s["kv_lora_rank"],
                rope=s["qk_rope_head_dim"])


def attend_roofline(ctx) -> Optional[Dict[str, Any]]:
    """The least time the chip could take for one decode run's absorbed
    attention (mla_flops: the FLOPs of every position read and the bytes
    of its one row) over the time the attention took, and which bound."""
    cap, r = attend_phases.capture(ctx), attend_phases.rows(ctx)
    if not cap or not r or not cap["attend_ms"] \
            or "kv_lora_rank" not in ctx["sizes"]:
        return None
    share, bound = flops.roofline_share_pct(
        mla_flops.absorbed_attend_flops(r["kv_rows_read"], **_widths(ctx)),
        mla_flops.absorbed_attend_bytes(r["kv_rows_read"],
                                        r["kv_row_bytes"]),
        cap["attend_ms"] / 1e3,
        ctx["peaks"].flops_per_s, ctx["peaks"].hbm_bytes_per_s)
    out = {"pct": share, "bound": bound}
    phases.note(ctx, "mla_attend_roofline", out)
    return out


def prefill_attend_roofline(ctx) -> Optional[Dict[str, Any]]:
    """Over the capture's prefill runs: the least time the chip could
    take for each run's expansion and causal attention at its BUCKET's
    length, summed, over the time they took, summed."""
    cap = capture(ctx)
    if not cap or not cap["prefill_attend_ms_by_bucket"]:
        return None
    s = ctx["sizes"]
    shape = dict(layers=s["kv_layers"], nope=s["qk_nope_head_dim"],
                 v=s["v_head_dim"], **_widths(ctx))
    least = took = 0.0
    for bucket, runs in cap["prefill_attend_ms_by_bucket"].items():
        t = int(bucket)
        least += len(runs) * max(
            mla_flops.prefill_attend_flops(t, **shape)
            / ctx["peaks"].flops_per_s,
            mla_flops.prefill_attend_bytes(t, **shape)
            / ctx["peaks"].hbm_bytes_per_s)
        took += sum(runs) / 1e3
    out = {"pct": 100.0 * least / took, "least_s": least, "took_s": took}
    phases.note(ctx, "mla_prefill_attend_roofline", out)
    return out
