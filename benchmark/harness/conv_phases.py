"""What the short-conv mixers' names say about a serving run (beside
phases.py, moe_phases.py, attend_phases.py and ssm_phases.py, which are
used as they are): from the capture the device time per decode run of the
operations under the ``conv.*`` scopes and under ``mlp.dense``
(models/lfm2.py); from the engine's counters (``stats()["state"]``, read by
ssm_phases.state_rows) the windows a decode run updated.  A program without
these names or counters gives every reader nothing to read: each returns
None.

There is no share of the roofline here, and why (my chip runs, PR 33): the
compiler prefetches most of the mixers' weights under OTHER layers'
operations, as asynchronous copies (``copy-start`` / ``copy-done``,
``slice-start`` / ``slice-done``) that carry no scope, so the mixers have
no time of their own to hold their bytes against.  Over the scoped time
alone the share read 155.9% (``conv.in_proj`` 0.119 ms where its 201 MB
take 0.246 at the chip's bandwidth); with every asynchronous copy of the
decode run added, 56.1%, which moves with other layers' prefetches; with
only the copies that carry the mixers' own weights (an operation's name
in the trace is its whole instruction, so a copy's operand names the
weight), 111.9%, because a ``-done``'s time is the wait for the one DMA
queue and not for its own bytes (the 12 KB of a layer's taps "wait" 0.053
ms).  ``mixer_floor`` puts the bytes and their least time beside
``conv.mixer_ms.sat`` in the info line, with the run's asynchronous
copies' time; the share is a `benchmark` issue's (PERF.md section 7)."""

from __future__ import annotations

from typing import Any, Dict, Optional

from . import conv_flops, phases, ssm_phases, trace as T

SCOPES = ("conv.in_proj", "conv.gate", "conv.window", "conv.out_proj")
DENSE = "mlp.dense"


def _filed_under(scope_path: Optional[str]) -> Optional[str]:
    parts = phases.scope_parts(scope_path or "")
    for scope in SCOPES + (DENSE,):
        if scope in parts:
            return scope
    return None


def _is_async_copy(op_name: str) -> bool:
    """One half of an asynchronous copy or slice (no collective: one
    chip)."""
    return T.opcode(op_name).endswith(("-start", "-done"))


def capture(ctx) -> Optional[Dict[str, Any]]:
    """Device milliseconds per decode run (a ``jit_fwd`` run that starts
    inside an ``llm.decode`` annotation) under each ``conv.*`` scope and
    under ``mlp.dense``."""
    if "_conv_capture" in ctx:
        return ctx["_conv_capture"]
    ctx["_conv_capture"] = out = _capture(ctx)
    if out:
        phases.note(ctx, "conv_capture", out)
    return out


def _capture(ctx) -> Optional[Dict[str, Any]]:
    tr = phases.again(ctx)
    if not tr or not tr.devices:
        return None
    lo, hi = T.window_of(tr)
    dev = tr.devices[0]
    decodes = ssm_phases._fwd_runs(dev, lo, hi,
                                   phases._spans(tr, "llm.decode"))
    scopes = phases.op_scopes(ctx["trace_path"])
    if not decodes or not scopes:
        return None
    ms: Dict[str, float] = {}
    copies: Dict[str, float] = {}
    for name, s, e in T._leaves(dev, lo, hi):
        if phases._covering(decodes, s) is None:
            continue
        scope = _filed_under(scopes.get(name))
        if scope is not None:
            ms[scope] = ms.get(scope, 0.0) + (e - s) / 1e6
        elif _is_async_copy(name):
            label = T.op_label(name)
            copies[label] = copies.get(label, 0.0) + (e - s) / 1e6
    if not any(k in ms for k in SCOPES):
        return None
    per_run = {k: v / len(decodes) for k, v in ms.items()}
    return {"decode_runs": len(decodes), "ms_by_scope": per_run,
            "mixer_ms": sum(per_run.get(k, 0.0) for k in SCOPES),
            "dense_ms": per_run.get(DENSE),
            "async_copy_ms": sum(copies.values()) / len(decodes),
            "async_copy_ms_by_op": {k: v / len(decodes)
                                    for k, v in sorted(copies.items())}}


def mixer_floor(ctx) -> Optional[Dict[str, Any]]:
    """For the info line, beside the mixers' scoped time: what one decode
    run's mixers must move (conv_flops.decode_mixer_bytes) and the least
    time the chip's memory could take for it.  Not a share of a roofline:
    the module docstring has the why."""
    cap, r = capture(ctx), ssm_phases.state_rows(ctx)
    if not cap or not r:
        return None
    nbytes = conv_flops.decode_mixer_bytes(
        r["state_rows_updated"], r["state_row_bytes"],
        r["mixer_weight_bytes"], ctx["sizes"]["conv_layers"])
    out = {"bytes": nbytes, "scoped_ms": cap["mixer_ms"],
           "least_ms": conv_flops.least_ms(
               nbytes, ctx["peaks"].hbm_bytes_per_s)}
    phases.note(ctx, "conv_mixer_floor", out)
    return out
