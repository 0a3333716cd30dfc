"""Operations and bytes the delta-rule (KDA) mixers need, computed from
shapes and from what the engine counted (beside flops.py, moe_flops.py,
ssm_flops.py, conv_flops.py and mla_flops.py).

A DECODE run's mixers (``decode_mixer_bytes``): every KDA layer's mixer
weights read once (``mixer_weight_bytes``: ``wq``, ``wk``, ``wv``, ``wo``, the
two low-rank gates, ``wb`` and the taps, as the engine reports them from the
model's config) and every slot the step moves read and written once (rows
of ``state_row_bytes``: the three windows and the float32 state).  The
program's step works BY SLOT over a layer's whole slab, so kda_phases.py
gives these functions every slot of the pool a layer (``slots_total`` x
KDA layers), not the running rows alone (``state_rows_updated``): a slot
whose sequence is not in the step is read and written back as it was, and
that traffic is the program's.  Where the batch is full the two agree.  The
rows in and out are left out.

The RECURRENCE alone (``step_bytes``): each moved slot's state, ``heads x
d_k x d_v`` float32, read and written once a layer.  Unlike a weight it
cannot be prefetched under another layer's operations: it is written by
the step before.  2 FLOPs a byte, so the memory bounds it.

The chunked SCAN of a prefill over a bucket of ``t`` positions
(``scan_flops`` / ``scan_bytes``): what the MATHEMATICS needs whatever
implements it.  The recurrent form costs a token a head ``7 d_k d_v``
FLOPs (the decay of ``S``, ``S^T k``, the rank-one update and ``S^T q``: 1 +
2 + 2 + 2 a state element), and q, k, v and the output (2 bytes each) and
the log-decay (4) are read or written once: 12 bytes a channel a token.
At 32 heads of 128 that is 3.67 MFLOP to 49 KB a token a layer, 75 FLOPs a
byte against the chip's 240: the memory term is the larger.  The chunked
form's own matmuls and its triangular solve are NOT counted: they are how,
not what.
"""

from __future__ import annotations

# the same convention and the same bandwidth floor as the short-conv mixers'
from .conv_flops import decode_mixer_bytes, least_ms  # noqa: F401


def step_bytes(rows: float, heads: int, head_dim: int) -> float:
    return 2.0 * rows * heads * head_dim * head_dim * 4


def scan_flops(t: int, layers: int, heads: int, head_dim: int) -> float:
    return 7.0 * head_dim * head_dim * heads * layers * t


def scan_bytes(t: int, layers: int, heads: int, head_dim: int) -> float:
    return 12.0 * heads * head_dim * layers * t
