"""What the experts' names say about a serving run (beside phases.py,
which is used as it is): the engine's routing counters over the window,
and from the capture the device time per decode run of the operations
under the ``moe.*`` scopes (ops/moe.py).  A program without these names
or counters gives every reader nothing to read: each returns None.

Read by hand off a v5e trace (my chip run, PR 26): the TPU compiler
rewrites ``jax.lax.ragged_dot`` into its own Mosaic kernels and gives
them ITS names, ``%ragged-dot-none.<n>`` (the grouped matmul) and
``%ragged-dot-metadata.<n>`` (group offsets from the sizes), with no
scope path: such a kernel is filed by its instruction's name, as the
flash kernels are, the matmul under ``moe.experts`` and the metadata
under ``moe.dispatch``."""

from __future__ import annotations

from typing import Any, Dict, Optional

from . import flops, moe_flops, phases, trace as T

ROUTE_SCOPES = ("moe.route", "moe.dispatch", "moe.combine")


def _filed_under(op_name: str, scope_path: Optional[str]):
    """The ``moe.*`` scope an operation's time goes to, or None."""
    label = T.op_label(op_name)     # the compiler's kernels, by name
    if label.startswith("ragged-dot-metadata"):
        return "moe.dispatch"
    if label.startswith("ragged-dot"):
        return "moe.experts"
    parts = phases.scope_parts(scope_path or "")
    for scope in ("moe.experts",) + ROUTE_SCOPES:
        if scope in parts:
            return scope
    return None


def routing(ctx) -> Optional[Dict[str, float]]:
    """Per decode run, from the deltas of ``stats()["moe"]`` over the
    window: layers, pairs and experts hit (summed over the layers)."""
    serve = ctx.get("serve") or {}
    a = (serve.get("before") or {}).get("moe")
    b = (serve.get("at_end") or {}).get("moe")
    layers = ctx["sizes"]["n_layer"]
    if not a or not b or b["layer_runs"] <= a["layer_runs"]:
        return None
    runs = (b["layer_runs"] - a["layer_runs"]) / layers
    out = {"runs": runs, "layers": layers,
           **{k: (b[k] - a[k]) / runs
              for k in ("pairs", "experts_hit", "max_load")}}
    phases.note(ctx, "moe_routing_per_run", out)
    return out


def capture(ctx) -> Optional[Dict[str, Any]]:
    """Device milliseconds per decode run under ``moe.experts`` and under
    the three scopes around it.  A decode run is a ``jit_fwd`` run that
    starts inside an ``llm.decode`` annotation (phases.serve_capture's
    rule); an operation belongs to the run it starts in."""
    if "_moe_capture" in ctx:
        return ctx["_moe_capture"]
    ctx["_moe_capture"] = out = _capture(ctx)
    if out:
        phases.note(ctx, "moe_capture", out)
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
    ms: Dict[str, float] = {}
    for name, s, e in T._leaves(dev, lo, hi):
        if phases._covering(runs, s) is None:
            continue
        scope = _filed_under(name, scopes.get(name))
        if scope:
            ms[scope] = ms.get(scope, 0.0) + (e - s) / 1e6
    if "moe.experts" not in ms:
        return None
    per_run = {k: v / len(runs) for k, v in ms.items()}
    return {"decode_runs": len(runs), "ms_by_scope": per_run,
            "experts_ms": per_run["moe.experts"],
            "route_ms": sum(per_run.get(k, 0.0) for k in ROUTE_SCOPES)}


def experts_roofline(ctx) -> Optional[Dict[str, Any]]:
    """The least time the chip could take for one decode run's expert
    matmuls over the time they took, and which bound."""
    cap, r = capture(ctx), routing(ctx)
    if not cap or not r:
        return None
    s = ctx["sizes"]
    share, bound = flops.roofline_share_pct(
        moe_flops.experts_flops(r["pairs"], s["d_model"], s["d_ff"]),
        moe_flops.experts_bytes(r["experts_hit"], r["pairs"], s["d_model"],
                                s["d_ff"]),
        cap["experts_ms"] / 1e3,
        ctx["peaks"].flops_per_s, ctx["peaks"].hbm_bytes_per_s)
    phases.note(ctx, "moe_experts_roofline", {"pct": share, "bound": bound})
    return {"pct": share, "bound": bound}
