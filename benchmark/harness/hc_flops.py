"""Operations and bytes a residual path of hyper-connections needs
(models/xing.py, steps 1-4 of its docstring), computed from shapes (beside
flops.py, moe_flops.py, mla_flops.py, ...): what a FUSED kernel would be
held to, for ``t`` positions of ``n`` streams of ``d`` numbers through
``sublayers`` sublayers.

Bytes, a position a sublayer: ONE read of ``X`` (n d numbers) for the
flattened norm and the projection, one for ``u``, one read and one write
for step 4, ``y`` read once (d), ``u`` written once (d): (4 n + 2) d
numbers of ``dtype_bytes``; and, a sublayer, ``Phi`` once in float32 (n d x
(2 n + n^2)).  The maps themselves (24 float32 numbers a position) are
left out as small.

FLOPs, a position a sublayer: the flattened norm (3 n d: square, sum,
scale), ``r Phi`` (2 n d (2 n + n^2)), ``u`` (2 n d), step 4 (2 n^2 d + 2 n
d); the Sinkhorn's ``iters`` rounds of 2 x (n^2 adds + n^2 divides) are
counted too (4 n^2 iters) though they are nothing beside the rest.  At n =
4, d = 3584: 18 d numbers = 129 KB in bf16 against ~1.0 MFLOP, 8 FLOPs a
byte against the chip's 240: the bytes bound it."""

from __future__ import annotations


def hc_bytes(t: int, sublayers: int, n: int, d: int,
             dtype_bytes: int = 2) -> float:
    per_position = (4 * n + 2) * d * dtype_bytes
    phi = n * d * (2 * n + n * n) * 4
    return sublayers * (t * per_position + phi)


def hc_flops(t: int, sublayers: int, n: int, d: int, iters: int) -> float:
    cols = 2 * n + n * n
    per_position = 3 * n * d + 2 * n * d * cols + 2 * n * d \
        + 2 * n * n * d + 2 * n * d + 4 * n * n * iters
    return float(sublayers) * t * per_position
