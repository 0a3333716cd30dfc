"""Operations and bytes latent attention (MLA) needs, computed from shapes
and from what the engine counted (beside flops.py, moe_flops.py,
ssm_flops.py and conv_flops.py).

The ABSORBED decode attention (ops/paged_attention.py
paged_decode_latent): every one of the ``heads`` meets the same row a
position.  A position read costs a head ``latent + rope`` multiply-adds for
its score (the absorbed no-rope query over ``c_kv``, the rotary query over
``k_pe``) and ``latent`` for its value (the same ``c_kv``): 2 x heads x
(2 latent + rope) FLOPs; the row is read ONCE, ``row_bytes`` with its
padding (what the kernel's copies move).  At 64 heads, 512 | 64 and 1,280
bytes that is 139,264 FLOPs to 1,280 bytes: 109 FLOPs a byte against the
chip's 240, so both terms count and ``flops.roofline_share_pct`` takes the
larger.  The query rows, the output rows and the page table are left out
as small; the absorbing matmuls are not attention and are not counted
here (``mla.proj_ms.sat`` holds their time).

The EXPANDED prefill attention over a bucket of ``t`` positions, a layer:
the expansion of every position's ``c_kv`` through ``W_kvb`` (2 x t x latent
x heads x (nope + v) FLOPs) and the causal triangle at keys of ``nope +
rope`` and values of ``v`` (2 x heads x t^2 / 2 x (nope + rope + v)).  At
the BUCKET's length, padding and all, since that is what runs; the kernel
pads v to the keys' width and computes whole blocks on the diagonal, and
neither is counted: the mathematics' FLOPs, so the share stays under 100%.
Bytes: q, k and v read and the output written once in bf16, and ``W_kvb``
and the ``c_kv`` rows read: far under the FLOPs' time at these shapes.
"""

from __future__ import annotations


def absorbed_attend_flops(rows_read: float, heads: int, latent: int,
                          rope: int) -> float:
    return rows_read * 2.0 * heads * (2 * latent + rope)


def absorbed_attend_bytes(rows_read: float, row_bytes: float) -> float:
    return rows_read * float(row_bytes)


def prefill_attend_flops(t: int, layers: int, heads: int, latent: int,
                         nope: int, rope: int, v: int) -> float:
    expand = 2.0 * t * latent * heads * (nope + v)
    triangle = 2.0 * heads * (t * t / 2.0) * (nope + rope + v)
    return layers * (expand + triangle)


def prefill_attend_bytes(t: int, layers: int, heads: int, latent: int,
                         nope: int, rope: int, v: int,
                         dtype_bytes: int = 2) -> float:
    per_position = heads * (2 * (nope + rope) + 2 * v) + latent
    return layers * dtype_bytes * (t * per_position
                                   + latent * heads * (nope + v))
