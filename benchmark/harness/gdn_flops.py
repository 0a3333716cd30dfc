"""Operations and bytes the Gated DeltaNet mixers and the K/V prefill need,
computed from shapes and from what the engine counted (beside kda_flops.py,
whose delta rule has square states and moves every slot of the pool).

The RECURRENCE of a decode run (``step_bytes``): each RUNNING row's state,
``heads x d_k x d_v`` float32 (30 x 96 x 192: 2,211,840 B), read and written
once a layer: the step kernel (ops/delta_rule.py) touches the running rows'
slots alone.  Counted on the state as the mathematics has it, UNPADDED:
where a layout pads it, the padding reads as a loss.  2 FLOPs a byte, so the
memory bounds it.

The chunked SCAN of a prefill over a bucket of ``t`` positions
(``scan_flops`` / ``scan_bytes``): what the MATHEMATICS needs whatever
implements it.  The recurrent form costs a token a head ``7 d_k d_v`` FLOPs
(the decay of ``S``, ``S^T k``, the rank-one update and ``S^T q``: 1 + 2 + 2
+ 2 a state element); q and k (``d_k`` each) and v and the output (``d_v``
each), 2 bytes a number, and the log-decay, ONE float32 a head, are read or
written once: ``2 (2 d_k + 2 d_v) + 4`` bytes a head a token.  At 30 heads
of 96 x 192 that is 3.87 MFLOP to 34.7 KB a token a layer, 112 FLOPs a byte
against the chip's 240: the memory term is the larger.  The chunked form's
own matmuls and its triangular solve are NOT counted: they are how, not
what.

The K/V PREFILL's attention (``prefill_attend_flops``): the causal triangle
of ``q k^T`` and ``p v`` among the bucket's own rows, ``2 x 2 x t^2 / 2 x
head_dim x heads`` a layer.  The kernel's whole diagonal blocks and the
bucket's padding are not counted.  Compute bounds it from a few hundred
rows on (its bytes are q, k, v and the output once: ``4 t heads head_dim x
2``).
"""

from __future__ import annotations

from .conv_flops import decode_mixer_bytes, least_ms  # noqa: F401


def step_bytes(rows: float, heads: int, d_k: int, d_v: int) -> float:
    return 2.0 * rows * heads * d_k * d_v * 4


def scan_flops(t: int, layers: int, heads: int, d_k: int, d_v: int) -> float:
    return 7.0 * d_k * d_v * heads * layers * t


def scan_bytes(t: int, layers: int, heads: int, d_k: int, d_v: int) -> float:
    return (2.0 * (2 * d_k + 2 * d_v) + 4) * heads * layers * t


def prefill_attend_flops(t: int, layers: int, heads: int,
                         head_dim: int) -> float:
    return 2.0 * 2.0 * t * t / 2.0 * head_dim * heads * layers


def prefill_attend_bytes(t: int, layers: int, heads: int,
                         head_dim: int) -> float:
    return 4.0 * t * heads * head_dim * 2 * layers
