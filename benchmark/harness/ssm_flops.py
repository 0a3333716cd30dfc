"""Bytes the state-space mixers of a decode run must move, computed from
what the engine counted (beside flops.py and moe_flops.py).

Convention: a decode step reads and writes once the recurrent state of
every running row in every state-space layer (``state_rows_updated`` rows
of ``state_row_bytes``: one sequence's conv window and state-space state
of one layer, as the engine reports them), and reads once the mixer's
matrices of every state-space layer (``mixer_weight_bytes``: ``in_proj``
and ``out_proj``; the conv's taps, ``A_log``, ``D``, ``dt_bias`` and the
norm's scale are left out as small, 0.2 MB of 205).  The rows in and out
(16 x 4096 x 2 bytes a layer) are left out too.  A LOWER bound on what
any program moves, so the share of the roofline cannot pass 100%: the
decode step is bound by the memory, not by arithmetic (2 FLOPs a weight
byte at 16 rows, 2 a state byte).
"""

from __future__ import annotations


def decode_mixer_bytes(state_rows_updated: float, state_row_bytes: float,
                       mixer_weight_bytes: float, layers: int) -> float:
    return 2.0 * state_rows_updated * state_row_bytes \
        + float(mixer_weight_bytes) * layers


def least_ms(nbytes: float, hbm_bytes_per_s: float) -> float:
    return 1e3 * nbytes / hbm_bytes_per_s
