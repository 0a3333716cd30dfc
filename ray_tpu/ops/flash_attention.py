"""Blockwise (flash) causal attention as a Pallas TPU kernel.

Why: dense attention materializes the [B, H, T, T] score matrix in HBM —
at GPT-2 pretraining shapes that is ~400 MB of fp32 traffic per pass and
the single largest bandwidth consumer in the step.  The blockwise kernel
keeps scores in VMEM with the online-softmax recurrence, so HBM sees only
Q/K/V/O (ref: the role of the reference's fused attention backends, e.g.
torch SDPA/FlashAttention used by release/train_tests LLM configs —
rebuilt here natively for the MXU rather than bound from a CUDA library).

Layout (said once, here): the kernels take q, k, v where the projection
left them and write ``out``, ``dq``, ``dk``, ``dv`` where the next one
reads them: ``[B, T, H*D]``, heads in the minor dimension, addressed by
BlockSpec index maps.  A block's minor dimension is 128 lanes: ONE head
of 128 to a program, or TWO adjacent heads of 64 (``packed``).  The two
are kept apart by zeroing the other head's lanes of q (and of dO): heads
of 64 half-fill the MXU's 128-deep tiles anyway, so contracting a zeroed
half against the whole 128-lane k (v) block costs no pass and adds exact
zeros; the row-side results (``acc``, ``dq``) take each head's lanes by
a select, the column-side ones (``dk``, ``dv``) come out with zeros in
the other head's lanes and are simply summed.  q, k and v may be three
column ranges of ONE ``[B, T, 3*H*D]`` array (``flash_attention_qkv``:
GPT-2's ``c_attn`` output, never split).  Every other shape (an odd
count of heads of 64, other head sizes) is ``folded``: transposed to
``[B*H, T, D]``, which the same kernels read as B*H rows of one head
each.  The choice is by shapes alone (``_packs``).  Grid (B, head
blocks, num_q_blocks, num_kv_blocks) with the kv axis innermost and
"arbitrary" semantics: per (b, head block, q-block) the kernel scans
kv blocks, maintaining running max/denominator (m, l) a head and an
fp32 accumulator in VMEM scratch.

Causal (``causal_schedule`` is the one description of it): grid blocks
above the diagonal are skipped (predicated off).  A grid block ON the
diagonal of 512 rows or more is not computed whole and masked: inside
the program it is walked in sub-blocks of 256 rows, q sub-block ``i``
against kv sub-blocks ``0..i`` only — one strip of scores a q sub-block,
whose last sub-block (the pair on the diagonal) alone builds and applies
the iota mask.  At T = 1024 with one 1024 block a head that is 10 of the
16 sub-block pairs, and a row's softmax is still ONE pass over its
strip, so the forward's output is the whole block's to the bit.  A
smaller diagonal block (ring attention's 256) is computed whole and
masked; blocks under the diagonal are computed whole, unmasked.

Backward: custom_vjp with the standard two-kernel flash backward — a
dkv kernel (grid over kv blocks, scanning q) and a dq kernel (grid over
q blocks, scanning kv), both recomputing P from the saved row-wise
log-sum-exp instead of reading a stored score matrix and delta =
rowsum(dO * O) from the ``out`` and ``dO`` blocks they hold, both
walking a diagonal block by the same strips as the forward.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..util import spans

_NEG_INF = -1e30
_LANES = 128
_ALL = slice(None)

_NT = (((1,), (1,)), ((), ()))    # a @ b^T
_NN = (((1,), (0,)), ((), ()))    # a @ b
_TN = (((0,), (0,)), ((), ()))    # a^T @ b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


# ---------------------------------------------------------- the schedule
def _visits(qi, ki, bq, bk):
    """Whether grid block (qi, ki) holds a pair on or under the diagonal
    (Python ints or program ids)."""
    return ki * bk <= qi * bq + bq - 1


def _sub_block(bq: int, bk: int, d: int) -> int:
    """Rows of a sub-block of the walk inside a diagonal grid block, from
    what the kernel sees; 0: no walk (the block is computed whole and
    masked).  Measured on v5e at T = 1024 with one 1024 block a head
    (PERF.md section 6, PR 34), the three kernels' ms a step of 12
    layers x 384 heads of 64: whole 69.4; strips of 512 rows 52.1, of
    256 rows 45.7, of 128 rows 49.3 (fewer pairs, but each key tile on
    the MXU then serves 128 rows); heads of 128 order the same way."""
    if bq != bk or bq < 512 or bq % 256:
        return 0
    return 256


class CausalSchedule(NamedTuple):
    """The causal walk of a ``[t, t]`` score square in ``bq x bk`` grid
    blocks.  ``sub``: rows (= columns) of a sub-block inside a diagonal
    grid block, 0 where every block is computed whole.  ``pairs``: the
    ``(i, j, masked)`` sub-block pairs a diagonal grid block computes,
    q sub-block ``i`` against kv sub-block ``j``; empty without a walk.
    ``visited`` / ``square``: pairs computed / pairs of the whole
    square, counted in sub-blocks (in grid blocks without a walk)."""
    sub: int
    pairs: Tuple[Tuple[int, int, bool], ...]
    visited: int
    square: int

    def strips(self):
        """[(q rows, kv rows)]: what the kernels loop over.  A q
        sub-block's kv sub-blocks are contiguous and the last is the
        masked one, so they are computed as ONE strip of scores whose
        trailing ``sub`` columns take the mask."""
        out = []
        for i in sorted({i for i, _, _ in self.pairs}):
            js = [j for qi, j, _ in self.pairs if qi == i]
            out.append((slice(i * self.sub, (i + 1) * self.sub),
                        slice(min(js) * self.sub, (max(js) + 1) * self.sub)))
        return out


def causal_schedule(t: int, bq: int, bk: int, d: int) -> CausalSchedule:
    """The ONE source of what the causal kernels compute: grid blocks
    above the diagonal are skipped; a diagonal grid block of
    ``_sub_block`` rows is walked over the sub-block pairs on or under
    its own diagonal, and only the pairs ON it are masked."""
    nq, nk = t // bq, t // bk
    blocks = sum(bool(_visits(qi, ki, bq, bk))
                 for qi in range(nq) for ki in range(nk))
    sub = _sub_block(bq, bk, d)
    if not sub:
        return CausalSchedule(0, (), blocks, nq * nk)
    n = bq // sub
    pairs = tuple((i, j, j == i) for i in range(n) for j in range(i + 1))
    return CausalSchedule(sub, pairs,
                          (blocks - nq) * n * n + nq * len(pairs),
                          nq * nk * n * n)


def _causal_mask(qi, ki, bq, bk):
    """(bq, bk) bool mask for the (qi, ki) block pair: row >= col."""
    rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return rows >= cols


def _over_block(causal, sched, qi, ki, bq, bk, tile):
    """Run a kernel's body ``tile(q rows, kv rows, mask)`` over what grid
    block (qi, ki) has to compute."""
    def whole(mask=None):
        return lambda: tile(_ALL, _ALL, mask and mask())

    def walk():
        diag = _causal_mask(0, 0, sched.sub, sched.sub)
        for r, c in sched.strips():
            tile(r, c, diag)

    if not causal:
        pl.when(True)(whole())
    elif not sched.sub:
        pl.when(_visits(qi, ki, bq, bk))(
            whole(lambda: _causal_mask(qi, ki, bq, bk)))
    else:
        if sched.square > (bq // sched.sub) ** 2:   # more than this block
            pl.when(ki < qi)(whole())
        pl.when(ki == qi)(walk)


def band_span(t: int, block: int, window: int) -> int:
    """k blocks of ``block`` rows that can meet the band ``0 <= i - j <
    window`` of one q block of as many rows: the diagonal one and those
    before it that hold a key less than ``window`` behind the block's
    first row; never more than the sequence has."""
    return min((window + block - 2) // block + 1, t // block)


def band_schedule(t: int, block: int, d: int, window: int
                  ) -> CausalSchedule:
    """The walk of a ``[t, t]`` score square under the band ``0 <= i - j <
    window`` in square grid blocks (``_over_band``).  The two edge blocks
    are walked in strips (``sub`` and ``pairs`` as the causal walk's, the
    far edge mirrored) where the causal kernel walks its diagonal AND the
    window is whole blocks; else ``sub`` is 0 and every block that meets
    the band is computed whole.  ``visited`` / ``square`` count
    sub-blocks (grid blocks without a walk)."""
    sched = causal_schedule(t, block, block, d)
    nq, span = t // block, band_span(t, block, window)
    blocks = sum(min(qi + 1, span) for qi in range(nq))
    if not sched.sub or window % block:
        return CausalSchedule(0, (), blocks, nq * nq)
    n = block // sched.sub
    far = sum(1 for qi in range(nq) if window // block <= min(qi, span - 1))
    edges = (nq + far) * len(sched.pairs)
    return CausalSchedule(sched.sub, sched.pairs,
                          (blocks - nq - far) * n * n + edges,
                          nq * nq * n * n)


def _over_band(call, sched, qi, back, tile):
    """Run the forward's body ``tile(q rows, kv rows, mask, lead)`` over
    what q block ``qi`` computes of the k block ``back`` blocks before
    its diagonal one, under the band ``0 <= i - j < call.window`` (blocks
    of ``bq`` = ``bk`` rows).  Blocks wholly inside the band are computed
    whole and unmasked; the diagonal block and the block the window's far
    edge crosses are the two EDGE blocks.  Where ``sched``
    (``band_schedule``) walks them in strips: the diagonal one as the
    causal kernel walks it, the far one mirrored (q sub-block ``i``
    against kv sub-blocks ``i..``, the first masked to the columns
    strictly above its diagonal).  Otherwise an edge block is computed
    whole under the band's mask."""
    blk, win = call.bq, call.window
    live = back <= qi                       # the k block exists
    far = back * blk + blk - 1 >= win       # holds a pair past the window

    def whole(mask=None):
        return lambda: tile(_ALL, _ALL, mask and mask(), False)

    def band():
        behind = back * blk \
            + jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0) \
            - jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
        return (behind >= 0) & (behind < win)

    pl.when(live & (back > 0) & jnp.logical_not(far))(whole())
    if not sched.sub:           # (``band_schedule`` says when)
        pl.when(live & ((back == 0) | far))(whole(band))
        return
    sub = sched.sub

    def near_edge():
        diag = _causal_mask(0, 0, sub, sub)
        for r, c in sched.strips():
            tile(r, c, diag, False)

    def far_edge():
        above = jnp.logical_not(_causal_mask(0, 0, sub, sub))
        for i in range(blk // sub):
            tile(slice(i * sub, (i + 1) * sub), slice(i * sub, blk),
                 above, True)

    pl.when(back == 0)(near_edge)
    if win // blk < call.span:              # the far edge's block is walked
        pl.when(live & (back == win // blk))(far_edge)


def _scores(q, k, scale, mask, lead=False):
    """float32 scores of the rows ``q`` against the keys ``k``; ``mask``
    (None: none) covers the whole tile or, narrower than it, the trailing
    columns of a strip: the square the diagonal crosses (``lead``: the
    LEADING columns, the square a window's far edge crosses)."""
    s = _dot(q, k, _NT) * scale                             # (rows, keys)
    if mask is None:
        return s
    w = mask.shape[1]
    if w == s.shape[1]:
        return jnp.where(mask, s, _NEG_INF)
    if lead:
        return jnp.concatenate(
            [jnp.where(mask, s[:, :w], _NEG_INF), s[:, w:]], axis=1)
    return jnp.concatenate(
        [s[:, :-w], jnp.where(mask, s[:, -w:], _NEG_INF)], axis=1)


def _lanes(x, n):
    """A per-row statistic kept replicated over 128 lanes, across ``n``
    lanes: no cross-lane broadcast where ``n`` is a multiple of 128."""
    if n <= _LANES:
        return x[:, :n]
    if n % _LANES:
        return x[:, :1]
    return jnp.tile(x, (1, n // _LANES))


# ------------------------------------------------------------ the layout
class _Call(NamedTuple):
    """What one call of the kernels is, hashable (the jitted wrappers'
    static argument).  The operands are ``[b, t, heads * d]`` with
    ``hpp`` heads to a program, so a block is ``hpp * d`` lanes wide;
    ``cols`` are the column blocks at which q, k and v start in their
    arrays (all 0 unless the three are ranges of one array)."""
    heads: int
    d: int
    hpp: int
    cols: Tuple[int, int, int]
    scale: float
    bq: int
    bk: int
    causal: bool
    interpret: bool
    # The forward alone (``flash_attention``'s ``window`` and grouped K/V):
    window: int = 0     # > 0: key j meets query i iff 0 <= i - j < window
    span: int = 0       # ... and the k blocks a q block then walks
    group: int = 1      # query heads that read ONE K/V head where it lies
    fold: bool = False  # K/V are [b * heads / group, t, d] (folded)

    @property
    def w(self):                     # lanes of a block
        return self.hpp * self.d

    @property
    def n(self):                     # programs (head blocks) a batch row
        return self.heads // self.hpp

    def schedule(self, t):
        if self.window:
            return band_schedule(t, self.bq, self.d, self.window)
        if self.causal:
            return causal_schedule(t, self.bq, self.bk, self.d)
        blocks = (t // self.bq) * (t // self.bk)
        return CausalSchedule(0, (), blocks, blocks)


def _packs(h: int, d: int) -> int:
    """Heads a program where ``[B, T, h * d]`` is read as it lies
    (``packed``): 1 of 128 lanes, 2 of 64; 0 where the shape has to be
    folded to ``[B*h, T, d]`` instead.  By shapes alone."""
    if d not in (64, _LANES) or (h * d) % _LANES:
        return 0
    return _LANES // d


def _head(x, a, call):
    """Head ``a`` of a block's lanes with the other head's zeroed:
    contracted against a whole block, the zeros add exact zeros."""
    if call.hpp == 1:
        return x
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    mine = lane < call.d if a == 0 else lane >= call.d
    return jnp.where(mine, x, jnp.zeros_like(x))


def _by_head(xs, call):
    """One block from a head's results each: head ``a``'s lanes from
    ``xs[a]`` (whose other lanes hold a cross term or a copy)."""
    if call.hpp == 1:
        return xs[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, xs[0].shape, 1)
    return jnp.where(lane < call.d, xs[0], xs[1])


def _column(ref, a, rows):
    """Rows of head ``a``'s per-row statistic kept lane-major,
    ``(hpp, 8, t)``, as a ``(rows, 1)`` column."""
    return ref[a, :1, rows].reshape(-1, 1)


def _specs(call, qi, ki):
    """BlockSpecs of a kernel over the grid ``(b, head block, x, y)``;
    ``qi`` / ``ki`` pick the q / kv block from ``(x, y)``."""
    def rows(n, col, at, group=1):
        if group == 1:
            def index(b, h, x, y):
                return b, at(x, y), col + h
        elif call.fold:         # a row of [b * h_kv, t, d] is a K/V head
            def index(b, h, x, y):
                return b // group, at(x, y), col + h
        else:                   # the group's block of [b, t, h_kv * d]
            def index(b, h, x, y):
                return b, at(x, y), col + h // group
        return pl.BlockSpec((1, n, call.w), index)

    cq, ck, cv = call.cols
    return {"q": rows(call.bq, cq, qi),
            "k": rows(call.bk, ck, ki, call.group),
            "v": rows(call.bk, cv, ki, call.group),
            "q_out": rows(call.bq, 0, qi), "kv_out": rows(call.bk, 0, ki),
            # per-row statistics: [b * heads, 8, t], see _fwd_kernel
            "stat": pl.BlockSpec(
                (call.hpp, 8, call.bq),
                lambda b, h, x, y: (b * call.n + h, 0, qi(x, y)))}


_Q_MAJOR = (lambda x, y: x, lambda x, y: y)     # grid (.., q block, kv block)
_KV_MAJOR = (lambda x, y: y, lambda x, y: x)    # grid (.., kv block, q block)
_PARAMS = dict(compiler_params=pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")))


# --------------------------------------------------------------- forward
def _softmax_step(q, k, v, m, l, scale, mask, lead=False):
    """One online-softmax update of the rows ``q`` by the keys ``k``:
    running max ``m`` and denominator ``l``, each (rows, 128) with its
    value in every lane (measured on v5e, PR 34: as (rows, 1) columns the
    whole-block forward takes 18.9 ms where this takes 16.6, to the same
    bits), float32.  Returns them with ``alpha``, by which the rows'
    accumulator shrinks, and ``P V``, which it gains."""
    # (``lead`` is said only by a window's far edge: the trainers' call is
    # the four arguments it was)
    s = _scores(q, k, scale, mask, lead) if lead \
        else _scores(q, k, scale, mask)
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - _lanes(m_new, s.shape[1]))
    alpha = jnp.exp(m - m_new)
    l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
    return m_new, l, alpha, _dot(p.astype(v.dtype), v, _NN)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, call, sched):
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    heads = range(call.hpp)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def tile(r, c, mask, lead=False):
        """Rows ``r`` of the q block attend rows ``c`` of the kv block."""
        q, k, v = q_ref[0, r, :], k_ref[0, c, :], v_ref[0, c, :]
        alphas, pvs = [], []
        for a in heads:
            m_scr[a, r, :], l_scr[a, r, :], alpha, pv = _softmax_step(
                _head(q, a, call), k, v, m_scr[a, r, :], l_scr[a, r, :],
                call.scale, mask, lead)
            alphas.append(_lanes(alpha, call.w))
            pvs.append(pv)
        acc_scr[r, :] = acc_scr[r, :] * _by_head(alphas, call) \
            + _by_head(pvs, call)

    if call.window:     # grid step ki is the k block span - 1 - ki back
        _over_band(call, sched, qi, call.span - 1 - ki, tile)
    else:
        _over_block(call.causal, sched, qi, ki, call.bq, call.bk, tile)

    @pl.when(ki == nk - 1)
    def _finalize():
        invs = []
        for a in heads:
            l = l_scr[a]
            invs.append(_lanes(
                jnp.where(l > 0, 1.0 / jnp.where(l > 0, l, 1.0), 0.0),
                call.w))
            lse = m_scr[a][:, :1] + jnp.log(jnp.maximum(l[:, :1], 1e-30))
            # (b * heads, 8, t) layout: TPU blocks need sublane dims
            # divisible by 8, so the per-row lse is replicated across 8
            # sublanes.
            lse_ref[a] = jnp.broadcast_to(lse.reshape(1, -1),
                                          (8, lse.shape[0]))
        o_ref[0] = (acc_scr[...] * _by_head(invs, call)).astype(o_ref.dtype)


def _qkv(ops):
    """(q, k, v) of a call's operands: three arrays, or ONE that holds
    them as column ranges (``call.cols`` says where)."""
    return ops if len(ops) == 3 else ops * 3


# Jitted: every layer of a model calls ONE traced and lowered function
# (PR 29's lesson: a kernel body is lowered once, not once a call site).
@functools.partial(jax.jit, static_argnames=("call",))
def _flash_forward(ops, *, call):
    q, k, v = _qkv(ops)
    b, t, _ = q.shape
    spec, nk = _specs(call, *_Q_MAJOR), t // call.bk
    if call.window:
        # Only the k blocks that meet the band: step y of q block x reads
        # the block span - 1 - y back (before the sequence's start: block
        # 0 again, which costs no copy, and the kernel leaves it out).
        nk = call.span
        spec = _specs(call, _Q_MAJOR[0], lambda x, y: jnp.maximum(
            x - (call.span - 1) + y, 0))
    with jax.named_scope("flash_fwd"):
        out, lse = pl.pallas_call(
            functools.partial(_fwd_kernel, call=call,
                              sched=call.schedule(t)),
            name="flash_fwd",
            grid=(b, call.n, t // call.bq, nk),
            in_specs=[spec["q"], spec["k"], spec["v"]],
            out_specs=[spec["q_out"], spec["stat"]],
            out_shape=[
                jax.ShapeDtypeStruct((b, t, call.heads * call.d), q.dtype),
                jax.ShapeDtypeStruct((b * call.heads, 8, t), jnp.float32),
            ],
            scratch_shapes=[
                # running max and denominator, a head
                pltpu.VMEM((call.hpp, call.bq, _LANES), jnp.float32),
                pltpu.VMEM((call.hpp, call.bq, _LANES), jnp.float32),
                pltpu.VMEM((call.bq, call.w), jnp.float32),  # accumulator
            ],
            interpret=call.interpret, **_PARAMS,
        )(q, k, v)
    return out, lse


# -------------------------------------------------------------- backward
def _p_ds(q, k, v, do, lse, delta, scale, mask):
    """P and dS = P * (dO V^T - delta) of the rows ``q`` against the keys
    ``k``, P recomputed from the rows' saved log-sum-exp."""
    p = jnp.exp(_scores(q, k, scale, mask) - lse)           # (rows, keys)
    return p, p * (_dot(do, v, _NT) - delta)


def _backward_refs(refs, with_dlse):
    """A backward kernel's refs: q, k, v, then what ``_rows_by_head``
    reads (``dlse`` only where the op exposes its lse), then the rest."""
    q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, *rest = refs
    dlse_ref = rest.pop(0) if with_dlse else None
    return (k_ref, v_ref, (q_ref, o_ref, do_ref, lse_ref, dlse_ref), *rest)


def _rows_by_head(refs, r, call):
    """The q rows ``r`` a head of the block: (q, dO, lse, delta), q and
    dO with the other head's lanes zeroed, the statistics as (rows, 1)
    columns.  delta = rowsum(dO * O) is computed here from the blocks
    the program holds anyway (summed over the block's whole lanes with
    the other head's zeroed; XLA, asked for it, lays the whole product
    out anew).  ``dlse``, the cotangent of an exposed log-sum-exp (ring
    attention's merge weights): d(lse_i)/d(s_ij) = p_ij, so it enters
    ds = p * (dp - delta + dlse) exactly like delta with the opposite
    sign."""
    q_ref, o_ref, do_ref, lse_ref, dlse_ref = refs
    q, do = q_ref[0, r, :], do_ref[0, r, :]
    prod = do.astype(jnp.float32) * o_ref[0, r, :].astype(jnp.float32)
    for a in range(call.hpp):
        delta = jnp.sum(_head(prod, a, call), axis=1, keepdims=True)
        if dlse_ref is not None:
            delta = delta - _column(dlse_ref, a, r)
        yield (_head(q, a, call), _head(do, a, call),
               _column(lse_ref, a, r), delta)


def _dkv_kernel(*refs, call, sched, with_dlse):
    k_ref, v_ref, rows, dk_ref, dv_ref, dk_scr, dv_scr = \
        _backward_refs(refs, with_dlse)
    ki, qi = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def tile(r, c, mask):
        k, v = k_ref[0, c, :], v_ref[0, c, :]
        for q, do, lse, delta in _rows_by_head(rows, r, call):
            p, ds = _p_ds(q, k, v, do, lse, delta, call.scale, mask)
            # zeros in the other head's lanes of q and dO: these land
            # in this head's lanes alone
            dv_scr[c, :] += _dot(p.astype(do.dtype), do, _TN)   # P^T @ dO
            dk_scr[c, :] += call.scale * _dot(ds.astype(q.dtype), q, _TN)

    _over_block(call.causal, sched, qi, ki, call.bq, call.bk, tile)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(*refs, call, sched, with_dlse):
    k_ref, v_ref, rows, dq_ref, dq_scr = _backward_refs(refs, with_dlse)
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def tile(r, c, mask):
        k, v = k_ref[0, c, :], v_ref[0, c, :]
        dqs = [_dot(_p_ds(q, k, v, do, lse, delta, call.scale, mask)[1]
                    .astype(k.dtype), k, _NN)                   # dS @ K
               for q, do, lse, delta in _rows_by_head(rows, r, call)]
        dq_scr[r, :] += call.scale * _by_head(dqs, call)

    _over_block(call.causal, sched, qi, ki, call.bq, call.bk, tile)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=("call",))
def _flash_backward(res, do, dlse=None, *, call):
    ops, out, lse = res
    q, k, v = _qkv(ops)
    b, t, _ = q.shape
    sched = call.schedule(t)
    nq, nk = t // call.bq, t // call.bk
    like = jax.ShapeDtypeStruct((b, t, call.heads * call.d), q.dtype)
    stats = (lse,) if dlse is None else (
        lse, jnp.broadcast_to(dlse.astype(jnp.float32)[:, None, :],
                              lse.shape))                  # (b*h, 8, t)
    kw = dict(call=call, sched=sched, with_dlse=dlse is not None)

    def operands(spec):
        return [spec["q"], spec["k"], spec["v"], spec["q_out"],
                spec["q_out"]] + [spec["stat"]] * len(stats)

    spec = _specs(call, *_KV_MAJOR)
    with jax.named_scope("flash_dkv"):
        dk, dv = pl.pallas_call(
            functools.partial(_dkv_kernel, **kw),
            name="flash_dkv",
            grid=(b, call.n, nk, nq),
            in_specs=operands(spec),
            out_specs=[spec["kv_out"], spec["kv_out"]],
            out_shape=[like, like],
            scratch_shapes=[pltpu.VMEM((call.bk, call.w), jnp.float32),
                            pltpu.VMEM((call.bk, call.w), jnp.float32)],
            interpret=call.interpret, **_PARAMS,
        )(q, k, v, out, do, *stats)

    spec = _specs(call, *_Q_MAJOR)
    with jax.named_scope("flash_dq"):
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, **kw),
            name="flash_dq",
            grid=(b, call.n, nq, nk),
            in_specs=operands(spec),
            out_specs=spec["q_out"],
            out_shape=like,
            scratch_shapes=[pltpu.VMEM((call.bq, call.w), jnp.float32)],
            interpret=call.interpret, **_PARAMS,
        )(q, k, v, out, do, *stats)
    if len(ops) == 3:
        return dq, dk, dv
    # One array came in, one cotangent goes back: two kernels cannot
    # write one array, so the three are joined here.
    return (jnp.concatenate([dq, dk, dv], axis=-1),)


# ------------------------------------------------------------- public op
@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _flash(ops, call):
    return _flash_forward(ops, call=call)[0]


def _flash_fwd(ops, call):
    out, lse = _flash_forward(ops, call=call)
    return out, (ops, out, lse)


def _flash_bwd(call, res, g):
    return (_flash_backward(res, g, call=call),)


_flash.defvjp(_flash_fwd, _flash_bwd)


# ------------------------------------------------------ forward-only op
# A window, or K/V read by group where they lie, is the serving prefill's:
# the backward kernels know neither, and say so.
@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _flash_served(ops, call):
    return _flash_forward(ops, call=call)[0]


def _flash_served_fwd(ops, call):
    return _flash_served(ops, call), None


def _flash_served_bwd(call, res, g):
    raise NotImplementedError(
        "flash_attention with a window or grouped K/V has no backward "
        "kernels: train through attn_impl='dense'")


_flash_served.defvjp(_flash_served_fwd, _flash_served_bwd)


# ------------------------------------------- partial (lse-exposing) op
# Same kernels, but the row-wise log-sum-exp is a real (differentiable)
# output: ring attention merges per-ring-step partial outputs with
# lse-derived weights (see parallel/ring_attention.py).
@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _flash_lse(ops, call):
    out, lse = _flash_forward(ops, call=call)
    return out, lse[:, 0, :]


def _flash_lse_fwd(ops, call):
    out, lse = _flash_forward(ops, call=call)
    return (out, lse[:, 0, :]), (ops, out, lse)


def _flash_lse_bwd(call, res, g):
    do, dlse = g
    return (_flash_backward(res, do, dlse, call=call),)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def _plan(t, h, d, causal, block_q, block_k, interpret, scale, fused=False,
          window=None, group=1):
    """What the public ops hand the kernels for ``h`` heads of ``d`` over
    ``t`` positions: the call (layout by ``_packs``, clamped blocks,
    scale) and the annotation that says, when the call is traced, which
    layout was taken and how much of the score square the kernels
    compute.  ``fused``: q, k, v are column ranges of one array."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_q = min(block_q, t)
    block_k = min(block_k, t)
    if t % block_q or t % block_k:
        raise ValueError(f"seq len {t} must divide block sizes "
                         f"({block_q}, {block_k})")
    hpp = _packs(h, d)
    # folded: a row of [B*h, T, d] is one head
    heads, per = (h, hpp) if hpp else (1, 1)
    n = heads // per
    call = _Call(heads, d, per, (0, n, 2 * n) if fused else (0, 0, 0),
                 d ** -0.5 if scale is None else float(scale),
                 block_q, block_k, bool(causal), bool(interpret))
    if window is not None or group != 1:
        if window is not None and (window < 1 or not causal
                                   or block_q != block_k):
            raise ValueError(
                f"a window ({window}) needs causal attention in square "
                f"blocks, got causal={causal}, blocks ({block_q}, "
                f"{block_k})")
        call = call._replace(
            window=window or 0, group=group, fold=not hpp,
            span=band_span(t, block_k, window) if window else 0)
    sched = call.schedule(t)
    note = spans.annotate("flash.schedule", t=t, block=block_q,
                          sub=sched.sub, visited=sched.visited,
                          square=sched.square,
                          layout="packed" if hpp else "folded",
                          heads_per_program=call.hpp,
                          **({"window": call.window, "span": call.span,
                              "group": call.group}
                             if call.window or call.group != 1 else {}))
    return call, note


def _run(op, q, k, v, **kw):
    """``op`` (``_flash`` or ``_flash_lse``) over q, k, v ``[B, T, H, D]``
    in the layout their shape allows; returns what ``op`` returns with
    ``out`` as ``[B, T, H, D]`` again."""
    b, t, h, d = q.shape
    group = h // k.shape[2]
    if group != 1:
        if _packs(h, d) == 2:
            # Two heads of 64 share a block's lanes: each needs its K/V
            # head in its own half, which only a copy gives.
            k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
        else:
            kw["group"] = group
    call, note = _plan(t, h, d, **kw)
    if call.heads == h:             # packed: merging H and D is a bitcast
        ops = tuple(x.reshape(b, t, -1) for x in (q, k, v))
    else:
        ops = tuple(x.transpose(0, 2, 1, 3).reshape(-1, t, d)
                    for x in (q, k, v))
    with note:
        res = op(ops, call)
    out, *rest = res if isinstance(res, tuple) else (res,)
    if call.heads == h:
        out = out.reshape(b, t, h, d)
    else:
        out = out.reshape(b, h, t, d).transpose(0, 2, 1, 3)
    return (out, *rest)


def flash_attention_with_lse(q, k, v, *, causal: bool = True,
                             block_q: int = 256, block_k: int = 256,
                             interpret: bool | None = None,
                             scale: float | None = None):
    """Flash attention that also returns the row log-sum-exp.

    q, k, v: [B, T, H, D] -> (out [B, T, H, D], lse [B, T, H] fp32).
    The lse output is differentiable (its cotangent folds into the
    backward's delta term), which makes this the building block for
    blockwise/ring attention merges."""
    b, t, h, _ = q.shape
    out, lse = _run(_flash_lse, q, k, v, causal=causal, block_q=block_q,
                    block_k=block_k, interpret=interpret, scale=scale)
    return out, lse.reshape(b, h, t).transpose(0, 2, 1)


def flash_attention(q, k, v, *, causal: bool = True,
                    block_q: int = 256, block_k: int = 256,
                    interpret: bool | None = None,
                    scale: float | None = None,
                    window: int | None = None):
    """Causal flash attention.  q, k, v: [B, T, H, D] -> [B, T, H, D];
    ``scale`` multiplies q k^T (None: ``D ** -0.5``).

    Forward only (differentiating it raises), for a serving prefill: k
    and v may hold FEWER heads than q, ``[B, T, H / group, D]``, and are
    then read where they lie, a query head's programs mapped to its
    group's block, never repeated to H heads (heads of 64, two to a
    block, are repeated here: see ``_run``); and with ``window`` key ``j``
    meets query ``i`` iff ``0 <= i - j < window``: the kernel's grid
    holds only the k blocks that meet that band (``band_span``) and masks
    the two edge blocks (``_over_band``).  ``window=None`` with as many
    K/V heads as query heads is the kernel every trainer runs.

    ``interpret=None`` auto-selects: the compiled kernel when the
    backend is ``tpu`` (never interpreted there unless a caller asks by
    argument; a kernel that cannot compile raises), the pallas
    interpreter on the CPU backend (so CPU-mesh tests exercise the
    same code).
    Block sizes must keep T % block == 0 (pretraining shapes are
    128-multiples; assert early rather than mask the tail).
    """
    kw = dict(causal=causal, block_q=block_q, block_k=block_k,
              interpret=interpret, scale=scale)
    if window is None and k.shape[2] == q.shape[2]:
        return _run(_flash, q, k, v, **kw)[0]
    return _run(_flash_served, q, k, v, window=window, **kw)[0]


def flash_attention_qkv(qkv, heads: int, *, causal: bool = True,
                        block_q: int = 256, block_k: int = 256,
                        interpret: bool | None = None,
                        scale: float | None = None):
    """``flash_attention`` over q, k and v as ONE projection left them:
    qkv ``[B, T, 3*H*D]`` (q's ``H*D`` columns, then k's, then v's) ->
    ``[B, T, H*D]``, what the output projection reads.  Where the shape
    packs, the kernels read the three as column ranges of ``qkv`` (no
    split is materialised) and ``qkv``'s cotangent is the three joined;
    where it does not, the array is split and folded as ever."""
    b, t, width = qkv.shape
    d = width // (3 * heads)
    kw = dict(causal=causal, block_q=block_q, block_k=block_k,
              interpret=interpret, scale=scale)
    if not _packs(heads, d):
        q, k, v = (x.reshape(b, t, heads, d)
                   for x in jnp.split(qkv, 3, axis=-1))
        return flash_attention(q, k, v, **kw).reshape(b, t, heads * d)
    call, note = _plan(t, heads, d, fused=True, **kw)
    with note:
        return _flash((qkv,), call)
