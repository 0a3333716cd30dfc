"""What the per-layer readers (benchmark/layer_metrics/<name>.py) share:
small functions over the run context ``ctx``.  A reader that finds nothing
to read returns None, and the harness leaves its metric out.

ctx keys: cell, kind, sizes, device, peaks, e2e, times, trace (the reduced
trace of a --trace 1 run, or None), and train or serve (what the runner
kept: per-step records, or request records and the engine's counters at
the window's start and end).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from . import flops
from .stats import median
from .trace import split_decode_prefill


# ------------------------------------------------------------- training

def traced_steps(ctx) -> int:
    return sum(1 for r in ctx["train"]["records"] if r["traced"])


def step_device_s(ctx) -> Optional[float]:
    """Device busy seconds per traced step (averaged over the chips)."""
    n = traced_steps(ctx) if ctx.get("train") else 0
    if not ctx.get("trace") or not n:
        return None
    return ctx["trace"]["busy_s"] / n


def step_device_ms(ctx) -> Optional[float]:
    dev = step_device_s(ctx)
    return None if dev is None else 1e3 * dev


def input_wait_share_pct(ctx) -> Optional[float]:
    recs = ctx["train"]["records"]
    return 100.0 * sum(r["wait_s"] for r in recs) \
        / sum(r["wall_s"] for r in recs)


def trainer_host_ms(ctx) -> Optional[float]:
    dev = step_device_s(ctx)
    if dev is None:
        return None
    wall = median([r["wall_s"] for r in ctx["train"]["records"]
                   if r["traced"]])
    return (wall - dev) * 1e3


def step_mfu_pct(ctx) -> Optional[float]:
    dev = step_device_s(ctx)
    if dev is None:
        return None
    return 100.0 * ctx["train"]["step_flops"] / (
        dev * ctx["peaks"].flops_per_s * ctx["cell"].chips)


def kernel_share_pct(ctx) -> Optional[float]:
    t = ctx.get("trace")
    if not t or not t["kernel_s"]:
        return None
    return 100.0 * t["kernel_s"] / t["busy_s"]


def flash_roofline(ctx) -> Optional[Dict[str, Any]]:
    """The flash kernels' share of their roofline, and which bound."""
    t, n = ctx.get("trace"), traced_steps(ctx)
    if not t or not t["kernel_s"] or not n:
        return None
    s, chips = ctx["sizes"], ctx["cell"].chips
    need = flops.flash_causal_train(
        ctx["cell"].traffic["global_batch"], s["n_head"],
        ctx["cell"].traffic["seq_len"], s["head_dim"], s["n_layer"])
    share, bound = flops.roofline_share_pct(
        need["flops"] / chips, need["bytes"] / chips, t["kernel_s"] / n,
        ctx["peaks"].flops_per_s, ctx["peaks"].hbm_bytes_per_s)
    return {"pct": share, "bound": bound}


def flash_roofline_pct(ctx) -> Optional[float]:
    r = flash_roofline(ctx)
    return None if r is None else r["pct"]


def exposed_collective_ms(ctx) -> Optional[float]:
    t, n = ctx.get("trace"), traced_steps(ctx)
    if not t or not n or ctx["cell"].chips < 2:
        return None
    return 1e3 * t["exposed_collective_s"] / n


# -------------------------------------------------------------- serving

def stats_delta(ctx, key: str) -> float:
    s = ctx["serve"]
    return s["at_end"][key] - s["before"][key]


def engine_step_ms(ctx) -> Optional[float]:
    n = stats_delta(ctx, "steps")
    return 1e3 * ctx["serve"]["counted_s"] / n if n else None


def engine_occupancy_pct(ctx) -> Optional[float]:
    n = stats_delta(ctx, "steps")
    return 100.0 * stats_delta(ctx, "tokens_generated") / (
        n * ctx["serve"]["engine"]["max_batch"]) if n else None


def decode_device_ms(ctx) -> Optional[float]:
    if not ctx.get("trace"):
        return None
    decode, _ = split_decode_prefill(ctx["trace"])
    return 1e3 * sum(decode) / len(decode) if decode else None
