"""Bytes the short-convolution mixers of a decode run must move, computed
from what the engine counted (beside flops.py, moe_flops.py and
ssm_flops.py).

Convention: a decode step reads once the mixer's weights of every
short-conv layer (``mixer_weight_bytes``: ``in_proj``, ``out_proj`` and the
taps, as the engine reports them from the model's config), and reads and
writes once the window of every running row in every such layer
(``state_rows_updated`` rows of ``state_row_bytes``: two rows of the
model's width, 8 KB at 2048 in bf16).  The rows in and out (16 x 2048 x 2
bytes a layer) are left out.  A LOWER bound on what any program moves: the
decode step is bound by the memory, not by arithmetic (2 FLOPs a weight
byte at 16 rows).  The weights are all but 0.1% of it: at 16 rows and 8
layers 268.5 MB of weights against 0.26 MB of windows.
"""

from __future__ import annotations

from .ssm_flops import least_ms  # noqa: F401 — the same bandwidth floor


def decode_mixer_bytes(state_rows_updated: float, state_row_bytes: float,
                       mixer_weight_bytes: float, layers: int) -> float:
    return float(mixer_weight_bytes) * layers \
        + 2.0 * state_rows_updated * state_row_bytes
