"""Operations and bytes the expert matmuls of a sparse layer need,
computed from shapes and from what the routing did (beside flops.py).

Convention: one (token, expert) pair passes through three ``d x f``
matrices (gate, up, down): 3 x 2 x d x f FLOPs.  The bytes are the
matrices of the experts that were HIT, each read once (a matrix whose
expert got no row is not read: counting all of them would put a decode
step's share over 100%), plus each pair's row in and row out; what lies
between (the ``f``-wide activations) a kernel could keep on the chip and
is not counted.
"""

from __future__ import annotations


def experts_flops(pairs: float, d: int, f: int) -> float:
    return pairs * 6.0 * d * f


def experts_bytes(experts_hit: float, pairs: float, d: int, f: int,
                  dtype_bytes: int = 2) -> float:
    return (experts_hit * 3.0 * d * f + pairs * 2.0 * d) * dtype_bytes
