"""Operations and bytes the attention of a model with sliding-window layers
needs (Command A+: benchmark/families/cohere2_moe.py), computed from shapes
and from what the engine counted.

A PREFILL's window layers (``band_flops``): query ``i`` of a bucket of ``t``
positions meets ``min(i + 1, window)`` keys, so the band holds ``sum_i min(i
+ 1, window)`` pairs (``band_pairs``), each 2 FLOPs of ``q k^T`` and 2 of ``p
v`` a head a head-dimension: ``4 x heads x head_dim x pairs`` a layer.  What
the kernel computes and masks away in its two edge blocks, and the bucket's
padding, are not counted.  Its bytes (``prefill_bytes``): q and the output
at the query heads' width, k and v at the K/V heads' (read where they lie:
nothing is repeated), once each, 2 bytes a number.  A FULL layer's prefill
is the same with the window as long as the bucket: the causal triangle, ``t
(t + 1) / 2`` pairs.

A DECODE run's window layers (``attend_bytes``): the rows of the rings the
kernel read, as the engine counted them (``stats()["attention"]``:
``window_rows_read``, a row one position's K and V of one layer,
``kv_row_bytes``).  A byte of K/V meets ``2 x heads / kv_heads`` FLOPs (16
query heads a K/V head: 32 a byte against the chip's 240), so the memory
bounds it.

``held_share``: the positions the two groups hold over what ONE group that
kept every position in every layer would.
"""

from __future__ import annotations


def band_pairs(t: int, window: int) -> float:
    """(query, key) pairs of ``0 <= i - j < window`` among ``t`` rows."""
    w = min(window, t)
    return w * (w + 1) / 2.0 + (t - w) * float(w)


def band_flops(t: int, layers: int, heads: int, head_dim: int,
               window: int) -> float:
    return 4.0 * heads * head_dim * band_pairs(t, window) * layers


def triangle_flops(t: int, layers: int, heads: int, head_dim: int) -> float:
    return band_flops(t, layers, heads, head_dim, t)


def prefill_bytes(t: int, layers: int, heads: int, kv_heads: int,
                  head_dim: int) -> float:
    return 2.0 * t * head_dim * (2 * heads + 2 * kv_heads) * layers


def attend_bytes(rows_read: float, row_bytes: int) -> float:
    return rows_read * row_bytes


def held_share(full_layers: int, window_layers: int, positions: int,
               window_positions: int) -> float:
    """Positions a row of the batch holds in the two groups, over what it
    would hold with every layer in the first."""
    return (full_layers * positions + window_layers * window_positions) \
        / float((full_layers + window_layers) * positions)


def least_ms(flops: float, nbytes: float, peak_flops: float,
             peak_bytes: float) -> float:
    return 1e3 * max(flops / peak_flops, nbytes / peak_bytes)
