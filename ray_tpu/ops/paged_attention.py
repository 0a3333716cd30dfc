"""Decode attention over the paged KV pool as a Pallas TPU kernel.

Why: for a ``[B, 1]`` decode step ``llm/kv_cache.py paged_attend`` gathers
every sequence's ``pages_per_seq`` pages into a second copy of K and V
(the whole pool, whatever the lengths), views the copy as ``[.., h, d]``
(a minor tile the TPU pads and therefore re-tiles) and computes scores
over ``max_context`` positions to mask most of them away.  This kernel
reads each sequence's pages where they lie in the pool, through the page
table, up to that sequence's length, and nothing else.

Layout: the pool stays ``[L, pages, page, h_kv*d]`` in HBM, heads folded
in the minor dimension, and is never sliced or viewed by head.  The
per-head arithmetic is put on the MXU by a block-diagonal query: row
``g`` of ``qmat [rows_g, h_kv*d]`` holds the query of head ``g`` in that
head's ``d`` lanes and zeros elsewhere, so ``qmat . k^T`` gives every
head's scores over a block of positions in one matmul of bf16 operands
accumulated in float32 (the products ``paged_attend``'s einsum makes),
and ``p . v`` gives ``[rows_g, h_kv*d]`` of which row ``g`` is read in
head ``g``'s lanes.  The MXU computes ``h`` times the useful products
and has nothing else to do in a decode step; the VPU touches only the
``[rows_g, block]`` scores, never K or V.  Grouped-query attention is
``rep = h / h_kv`` such problems over the same K and V: query head
``g * rep + r`` is row ``g`` of problem ``r``.

Grid (B,): one step a sequence.  Inside it the kernel loops over blocks
of ``block_pages`` pages, double-buffered: each page is one async copy
HBM -> VMEM (a row-major ``[page, h_kv*d]`` tile), started a block ahead,
and only pages below ``ceil(length / page)`` are copied at all.  Online
softmax in float32 across the blocks (running max and sum per head),
positions >= length masked.  A sequence of length 0 reads nothing and
writes zeros.

``paged_decode_latent`` is the same walk over a LATENT pool (``llm/
kv_cache.py``: one row a position a layer, ``[c_kv | k_pe | zeros]``,
shared by every head; ``models/kimi.py``).  All the heads meet the same
row, so the query is no block-diagonal: ``q [H, row]`` holds each head's
absorbed no-rope query over ``c_kv``'s lanes and its rotary query over
``k_pe``'s (zeros over the padding), one matmul against a block of rows
gives every head's scores, and the values are the first ``latent``
lanes of the rows ALREADY in VMEM for the scores: a page is copied once
(one async copy, not a K and a V one).  Blocks of ``LATENT_BLOCK_PAGES``
pages: 512 positions of 1,280 bytes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
# Pages a block holds: 8 x 16 = 128 positions, one MXU tile of keys.
BLOCK_PAGES = 8


# Pages a block of the latent kernel holds: 32 x 16 = 512 positions, a
# [512, 640] bf16 buffer of 640 KB (two of them), 32 copies of 20 KB.
LATENT_BLOCK_PAGES = 32


def _sublanes(dtype) -> int:
    """Rows of one TPU tile of ``dtype``: 8 of float32, 16 of bf16."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def supported(q, k_pages) -> bool:
    """Whether the compiled kernel takes these shapes: a decode step
    whose folded K/V rows and pages are whole TPU tiles."""
    return (q.shape[1] == 1 and k_pages.shape[3] % 128 == 0
            and k_pages.shape[2] % _sublanes(k_pages.dtype) == 0)


def _decode_kernel(layer_ref, table_ref, len_ref, q_ref, k_hbm, v_hbm,
                   o_ref, k_buf, v_buf, sems, m_scr, l_scr, acc_scr,
                   *, scale, d, rep, rows_g, page, block_pages):
    b = pl.program_id(0)
    layer, length = layer_ref[0], len_ref[b]
    pages_per_seq = table_ref.shape[1]
    rows = block_pages * page
    hd = k_buf.shape[2]
    n_pages = (length + page - 1) // page
    n_blocks = (n_pages + block_pages - 1) // block_pages

    def each_page(blk, slot, act):
        """``act`` on the K and V copy of every live page of a block."""
        for i in range(block_pages):
            p = blk * block_pages + i

            @pl.when(p < n_pages)
            def _(i=i, p=p):
                ix = table_ref[b, jnp.minimum(p, pages_per_seq - 1)]
                dst = pl.ds(i * page, page)
                act(pltpu.make_async_copy(
                    k_hbm.at[layer, ix], k_buf.at[slot, dst],
                    sems.at[slot, 0]))
                act(pltpu.make_async_copy(
                    v_hbm.at[layer, ix], v_buf.at[slot, dst],
                    sems.at[slot, 1]))

    @pl.when(b == 0)
    def _clear():
        # Rows no copy has written yet meet a probability of exactly 0:
        # they must hold numbers.  (K needs none: its scores are masked.)
        v_buf[...] = jnp.zeros_like(v_buf)

    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    # Lane l belongs to K/V head l // d: row g keeps head g's lanes.
    g_ix = jax.lax.broadcasted_iota(jnp.int32, (rows_g, hd), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows_g, hd), 1)
    own = (lane >= g_ix * d) & (lane < (g_ix + 1) * d)
    q = q_ref[0].astype(jnp.float32)                    # (rep, hd)
    qmat = jnp.concatenate(
        [jnp.where(own, jnp.broadcast_to(q[r:r + 1], (rows_g, hd)), 0.0)
         for r in range(rep)], axis=0).astype(k_buf.dtype)

    @pl.when(n_blocks > 0)
    def _first():
        each_page(0, 0, lambda c: c.start())

    def block(blk, _):
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _ahead():
            each_page(blk + 1, 1 - slot, lambda c: c.start())

        each_page(blk, slot, lambda c: c.wait())
        k, v = k_buf[slot], v_buf[slot]                 # (rows, hd)
        s = jax.lax.dot_general(
            qmat, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (R, rows)
        pos = blk * rows + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, _NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    jax.lax.fori_loop(0, n_blocks, block, None)

    l = l_scr[:, :1]
    inv = jnp.where(l > 0, 1.0 / jnp.where(l > 0, l, 1.0), 0.0)
    out = acc_scr[...] * inv                            # (R, hd)
    for r in range(rep):
        mine = jnp.where(own, out[r * rows_g:(r + 1) * rows_g], 0.0)
        o_ref[0, r:r + 1, :] = jnp.sum(
            mine, axis=0, keepdims=True).astype(o_ref.dtype)


def paged_decode(q, k_pages, v_pages, layer, page_table, lengths,
                 *, block_pages: int = BLOCK_PAGES,
                 interpret: bool | None = None,
                 scale: float | None = None):
    """Attention of one query row a sequence, q ``[B, 1, h, d]``, against
    layer ``layer`` of the WHOLE pool ``[L, pages, page, h_kv*d]``:
    sequence ``b`` attends positions ``0 .. lengths[b] - 1``, which live
    in the pages ``page_table[b]`` names (entries beyond its pages are
    never read).  Returns ``[B, 1, h, d]`` in q's dtype; a row of length
    0 gives zeros.  float32 scores and softmax over K/V as stored: the
    mathematics of ``paged_attend``, which is its plain definition
    (``scale`` multiplies the scores; None: ``d ** -0.5``).

    ``interpret=None`` runs the compiled kernel on the ``tpu`` backend
    and the interpreter elsewhere (tests)."""
    if q.shape[1] != 1:
        raise ValueError(f"paged_decode takes one query row a sequence, "
                         f"got {q.shape[1]}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # The layer is an operand, so every layer of a model calls ONE traced
    # and lowered function: 36 call sites cost the tracing of one.
    return _paged_decode(
        q, k_pages, v_pages, jnp.asarray(layer, jnp.int32).reshape(1),
        page_table.astype(jnp.int32), lengths.astype(jnp.int32),
        block_pages=block_pages, interpret=interpret,
        scale=q.shape[-1] ** -0.5 if scale is None else float(scale))


@functools.partial(jax.jit,
                   static_argnames=("block_pages", "interpret", "scale"))
def _paged_decode(q, k_pages, v_pages, layer, page_table, lengths, *,
                  block_pages, interpret, scale):
    b, _, h, d = q.shape
    page, hd = k_pages.shape[2], k_pages.shape[3]
    h_kv = hd // d
    rep = h // h_kv
    # Query head g * rep + r is row r, lanes of K/V head g.
    qf = q.reshape(b, h_kv, rep, d).transpose(0, 2, 1, 3) \
        .reshape(b, rep, hd)
    sublanes = _sublanes(k_pages.dtype)
    rows_g = -(-h_kv // sublanes) * sublanes
    rows = block_pages * page
    kernel = functools.partial(
        _decode_kernel, scale=scale, d=d, rep=rep, rows_g=rows_g,
        page=page, block_pages=block_pages)
    with jax.named_scope("kv.attend"):
        out = pl.pallas_call(
            kernel,
            name="paged_decode",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(b,),
                in_specs=[
                    pl.BlockSpec((1, rep, hd), lambda i, *_: (i, 0, 0)),
                    pl.BlockSpec(memory_space=pl.ANY),
                    pl.BlockSpec(memory_space=pl.ANY),
                ],
                out_specs=pl.BlockSpec((1, rep, hd),
                                       lambda i, *_: (i, 0, 0)),
                scratch_shapes=[
                    pltpu.VMEM((2, rows, hd), k_pages.dtype),
                    pltpu.VMEM((2, rows, hd), v_pages.dtype),
                    pltpu.SemaphoreType.DMA((2, 2)),
                    pltpu.VMEM((rep * rows_g, 128), jnp.float32),  # max
                    pltpu.VMEM((rep * rows_g, 128), jnp.float32),  # sum
                    pltpu.VMEM((rep * rows_g, hd), jnp.float32),
                ]),
            out_shape=jax.ShapeDtypeStruct((b, rep, hd), q.dtype),
            # One core walks the sequences in order: the V buffers are
            # cleared once, at the first.
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(layer, page_table, lengths, qf, k_pages, v_pages)
    return out.reshape(b, rep, h_kv, d).transpose(0, 2, 1, 3) \
        .reshape(b, 1, h, d)


# ------------------------------------------------------ the latent pool
def _latent_kernel(layer_ref, table_ref, len_ref, q_ref, kv_hbm, o_ref,
                   buf, sems, m_scr, l_scr, acc_scr,
                   *, scale, latent, page, block_pages):
    b = pl.program_id(0)
    layer, length = layer_ref[0], len_ref[b]
    pages_per_seq = table_ref.shape[1]
    rows = block_pages * page
    n_pages = (length + page - 1) // page
    n_blocks = (n_pages + block_pages - 1) // block_pages

    def each_page(blk, slot, act):
        """``act`` on the copy of every live page of a block."""
        for i in range(block_pages):
            p = blk * block_pages + i

            @pl.when(p < n_pages)
            def _(i=i, p=p):
                ix = table_ref[b, jnp.minimum(p, pages_per_seq - 1)]
                act(pltpu.make_async_copy(
                    kv_hbm.at[layer, ix],
                    buf.at[slot, pl.ds(i * page, page)], sems.at[slot]))

    @pl.when(b == 0)
    def _clear():
        # Rows no copy has written yet meet a probability of exactly 0
        # as VALUES: they must hold numbers.
        buf[...] = jnp.zeros_like(buf)

    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    q = q_ref[0]                                        # (H, row)

    @pl.when(n_blocks > 0)
    def _first():
        each_page(0, 0, lambda c: c.start())

    def block(blk, _):
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _ahead():
            each_page(blk + 1, 1 - slot, lambda c: c.start())

        each_page(blk, slot, lambda c: c.wait())
        kv = buf[slot]                                  # (rows, row)
        s = jax.lax.dot_general(
            q, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (H, rows)
        pos = blk * rows + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, _NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(kv.dtype), kv[:, :latent], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    jax.lax.fori_loop(0, n_blocks, block, None)

    l = l_scr[:, :1]
    inv = jnp.where(l > 0, 1.0 / jnp.where(l > 0, l, 1.0), 0.0)
    o_ref[0] = (acc_scr[...] * inv).astype(o_ref.dtype)


def latent_supported(q_lat, pages) -> bool:
    """Whether the compiled latent kernel takes these shapes: a decode
    step, rows and pages whole TPU tiles, the latent part too."""
    return (q_lat.shape[1] == 1 and pages.shape[3] % 128 == 0
            and q_lat.shape[3] % 128 == 0
            and pages.shape[2] % _sublanes(pages.dtype) == 0)


def paged_decode_latent(q_lat, q_pe, pages, layer, page_table, lengths,
                        *, scale: float,
                        block_pages: int = LATENT_BLOCK_PAGES,
                        interpret: bool | None = None):
    """Attention of one query row a sequence in the latent space:
    ``q_lat`` ``[B, 1, H, latent]`` and ``q_pe`` ``[B, 1, H, rope]``
    against layer ``layer`` of the WHOLE latent pool ``[L, pages, page,
    row]``; sequence ``b`` attends positions ``0 .. lengths[b] - 1`` in
    the pages ``page_table[b]`` names.  Returns ``o_lat`` ``[B, 1, H,
    latent]`` in q's dtype; a row of length 0 gives zeros.  The
    mathematics of ``llm/kv_cache.py latent_attend``, its plain
    definition."""
    if q_lat.shape[1] != 1:
        raise ValueError("paged_decode_latent takes one query row a "
                         f"sequence, got {q_lat.shape[1]}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, _, h, r = q_lat.shape
    pad = pages.shape[3] - r - q_pe.shape[3]
    # One query over the row's lanes: [q_lat | q_pe | zeros].
    q = jnp.concatenate(
        [q_lat[:, 0], q_pe[:, 0].astype(q_lat.dtype),
         jnp.zeros((b, h, pad), q_lat.dtype)], axis=-1).astype(pages.dtype)
    out = _paged_decode_latent(
        q, pages, jnp.asarray(layer, jnp.int32).reshape(1),
        page_table.astype(jnp.int32), lengths.astype(jnp.int32),
        latent=r, block_pages=block_pages, interpret=interpret,
        scale=float(scale))
    return out[:, None].astype(q_lat.dtype)


@functools.partial(jax.jit, static_argnames=(
    "latent", "block_pages", "interpret", "scale"))
def _paged_decode_latent(q, pages, layer, page_table, lengths, *, latent,
                         block_pages, interpret, scale):
    b, h, width = q.shape
    page = pages.shape[2]
    rows = block_pages * page
    kernel = functools.partial(_latent_kernel, scale=scale, latent=latent,
                               page=page, block_pages=block_pages)
    with jax.named_scope("kv.attend"):
        return pl.pallas_call(
            kernel,
            name="paged_decode_latent",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(b,),
                in_specs=[
                    pl.BlockSpec((1, h, width), lambda i, *_: (i, 0, 0)),
                    pl.BlockSpec(memory_space=pl.ANY),
                ],
                out_specs=pl.BlockSpec((1, h, latent),
                                       lambda i, *_: (i, 0, 0)),
                scratch_shapes=[
                    pltpu.VMEM((2, rows, width), pages.dtype),
                    pltpu.SemaphoreType.DMA((2,)),
                    pltpu.VMEM((h, 128), jnp.float32),      # max
                    pltpu.VMEM((h, 128), jnp.float32),      # sum
                    pltpu.VMEM((h, latent), jnp.float32),
                ]),
            out_shape=jax.ShapeDtypeStruct((b, h, latent), q.dtype),
            # One core walks the sequences in order: the buffers are
            # cleared once, at the first.
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(layer, page_table, lengths, q, pages)
