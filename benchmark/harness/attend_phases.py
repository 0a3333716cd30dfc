"""What the decode step's attention over the paged cache costs (beside
phases.py and moe_phases.py, which are used as they are): from the
capture the device time per decode run of the operations under the scope
``kv.attend`` (llm/kv_cache.py ``paged_attend``; ops/paged_attention.py)
plus the kernel ``paged_decode``, which is filed by its instruction's
name as the flash kernels are; from the engine's counters the K/V rows
that attention read.  A program without the scope or the counter gives a
reader nothing to read: it returns None.

Bytes convention: one row is one position's K and one position's V of one
layer, ``kv_row_bytes`` = 2 x h_kv x d x the pool's item size, as the
engine reports it.  ``kv_rows_read`` counts what the kernel's copies move
(each running row's length rounded up to whole pages, the unit the kernel
copies, times the layers), never the ``max_batch x max_context`` rows the
gather moved: those are ``kv_rows_held``.  The query row, the output row
and the page table are left out as small (16 x 2.5 KB a layer)."""

from __future__ import annotations

from typing import Any, Dict, Optional

from . import phases, trace as T

KERNEL = "paged_decode"
SCOPE = "kv.attend"


def _is_kernel(op_name: str) -> bool:
    return op_name.split(" ", 1)[0].lstrip("%").startswith(KERNEL)


def _is_attend(op_name: str, scope_path: Optional[str]) -> bool:
    parts = phases.scope_parts(scope_path or "")
    return _is_kernel(op_name) or SCOPE in parts or KERNEL in parts


def capture(ctx) -> Optional[Dict[str, Any]]:
    """Device milliseconds per decode run of the attention over the paged
    cache.  A decode run is a ``jit_fwd`` run that starts inside an
    ``llm.decode`` annotation (moe_phases.capture's rule); an operation
    belongs to the run it starts in."""
    if "_attend_capture" in ctx:
        return ctx["_attend_capture"]
    ctx["_attend_capture"] = out = _capture(ctx)
    if out:
        phases.note(ctx, "attend_capture", out)
    return out


def _capture(ctx) -> Optional[Dict[str, Any]]:
    tr = phases.again(ctx)
    if not tr or not tr.devices:
        return None
    lo, hi = T.window_of(tr)
    dev = tr.devices[0]
    decodes = phases._spans(tr, "llm.decode")
    runs = sorted((s, s + d) for name, s, d in dev.modules
                  if name.split("(", 1)[0] == "jit_fwd"
                  and lo <= s + d / 2 <= hi
                  and phases._covering(decodes, s) is not None)
    scopes = phases.op_scopes(ctx["trace_path"])
    if not runs or not scopes:
        return None
    by_label: Dict[str, float] = {}
    kernel_ms = store_ms = program_ms = 0.0
    for name, s, e in T._leaves(dev, lo, hi):
        if phases._covering(runs, s) is None:
            continue
        ms = (e - s) / 1e6
        program_ms += ms
        scope = scopes.get(name)
        if _is_attend(name, scope):
            label = T.op_label(name)
            by_label[label] = by_label.get(label, 0.0) + ms
            kernel_ms += ms if _is_kernel(name) else 0.0
        elif "kv.store" in phases.scope_parts(scope or ""):
            store_ms += ms
    if not by_label:
        return None
    n = len(runs)
    top = sorted(by_label.items(), key=lambda kv: -kv[1])[:8]
    return {"decode_runs": n,
            "attend_ms": sum(by_label.values()) / n,
            "kernel_ms": kernel_ms / n,
            "store_ms": store_ms / n,
            "decode_ops_ms": program_ms / n,
            "attend_ms_by_op": {k: v / n for k, v in top}}


def rows(ctx) -> Optional[Dict[str, float]]:
    """Per decode run, from the deltas of ``stats()["attention"]`` over
    the window: K/V rows read and held, and the bytes of one row."""
    serve = ctx.get("serve") or {}
    a = (serve.get("before") or {}).get("attention")
    b = (serve.get("at_end") or {}).get("attention")
    if not a or not b or b["decode_runs"] <= a["decode_runs"]:
        return None
    runs = b["decode_runs"] - a["decode_runs"]
    out = {"runs": runs,
           "kv_rows_read": (b["kv_rows_read"] - a["kv_rows_read"]) / runs,
           "kv_rows_held": (b["kv_rows_held"] - a["kv_rows_held"]) / runs,
           "kv_row_bytes": b["kv_row_bytes"]}
    out["read_over_held"] = out["kv_rows_read"] / out["kv_rows_held"]
    phases.note(ctx, "attend_rows_per_run", out)
    return out


def attend_roofline(ctx) -> Optional[Dict[str, Any]]:
    """The least time the chip's memory could take to read one decode
    run's live K/V rows, over the time the attention took."""
    cap, r = capture(ctx), rows(ctx)
    if not cap or not r or not cap["attend_ms"]:
        return None
    nbytes = r["kv_rows_read"] * r["kv_row_bytes"]
    least_ms = 1e3 * nbytes / ctx["peaks"].hbm_bytes_per_s
    out = {"pct": 100.0 * least_ms / cap["attend_ms"],
           "bytes": nbytes, "least_ms": least_ms}
    phases.note(ctx, "attend_roofline", out)
    return out
