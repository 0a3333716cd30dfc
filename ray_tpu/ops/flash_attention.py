"""Blockwise (flash) causal attention as a Pallas TPU kernel.

Why: dense attention materializes the [B, H, T, T] score matrix in HBM —
at GPT-2 pretraining shapes that is ~400 MB of fp32 traffic per pass and
the single largest bandwidth consumer in the step.  The blockwise kernel
keeps scores in VMEM with the online-softmax recurrence, so HBM sees only
Q/K/V/O (ref: the role of the reference's fused attention backends, e.g.
torch SDPA/FlashAttention used by release/train_tests LLM configs —
rebuilt here natively for the MXU rather than bound from a CUDA library).

Layout: q, k, v are [BH, T, D] (batch*heads folded — each program works
on one head).  Grid (BH, num_q_blocks, num_kv_blocks) with the kv axis
innermost and "arbitrary" semantics: per (bh, q-block) the kernel scans
kv blocks, maintaining running max/denominator (m, l) and an fp32
accumulator in VMEM scratch.

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
log-sum-exp instead of reading a stored score matrix, both walking a
diagonal block by the same strips as the forward.
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


def _scores(q, k, scale, mask):
    """float32 scores of the rows ``q`` against the keys ``k``; ``mask``
    (None: none) covers the whole tile or, narrower than it, the trailing
    columns of a strip: the square the diagonal crosses."""
    s = _dot(q, k, _NT) * scale                             # (rows, keys)
    if mask is None:
        return s
    w = mask.shape[1]
    if w == s.shape[1]:
        return jnp.where(mask, s, _NEG_INF)
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


def _column(ref, rows):
    """Rows of a per-row statistic kept lane-major, ``(1, 8, t)``, as a
    ``(rows, 1)`` column."""
    return ref[0, :1, rows].reshape(-1, 1)


# --------------------------------------------------------------- forward
def _softmax_step(q, k, v, m, l, acc, scale, mask):
    """One online-softmax update of the rows ``q`` by the keys ``k``:
    running max ``m`` and denominator ``l``, each (rows, 128) with its
    value in every lane (measured on v5e, PR 34: as (rows, 1) columns the
    whole-block forward takes 18.9 ms where this takes 16.6, to the same
    bits), accumulator ``acc`` (rows, d), all float32."""
    s = _scores(q, k, scale, mask)
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - _lanes(m_new, s.shape[1]))
    alpha = jnp.exp(m - m_new)
    l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
    acc = acc * _lanes(alpha, acc.shape[1]) \
        + _dot(p.astype(v.dtype), v, _NN)
    return m_new, l, acc


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, bq, bk, causal, sched):
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def tile(r, c, mask):
        """Rows ``r`` of the q block attend rows ``c`` of the kv block."""
        m_scr[r, :], l_scr[r, :], acc_scr[r, :] = _softmax_step(
            q_ref[0, r, :], k_ref[0, c, :], v_ref[0, c, :],
            m_scr[r, :], l_scr[r, :], acc_scr[r, :], scale, mask)

    _over_block(causal, sched, qi, ki, bq, bk, tile)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[:, :1]
        inv = jnp.where(l > 0, 1.0 / jnp.where(l > 0, l, 1.0), 0.0)
        o_ref[0] = (acc_scr[...] * inv).astype(o_ref.dtype)
        lse = m_scr[:, :1] + jnp.log(jnp.maximum(l, 1e-30))
        # (bh, 8, t) layout: TPU blocks need sublane dims divisible by 8,
        # so the per-row lse is replicated across 8 sublanes.
        lse_ref[0] = jnp.broadcast_to(lse.reshape(1, -1),
                                      (8, lse.shape[0]))


# Jitted: every layer of a model calls ONE traced and lowered function
# (PR 29's lesson: a kernel body is lowered once, not once a call site).
@functools.partial(jax.jit, static_argnames=("scale", "bq", "bk", "causal",
                                             "interpret"))
def _flash_forward(q, k, v, *, scale, bq, bk, causal, interpret):
    bh, t, d = q.shape
    nq, nk = pl.cdiv(t, bq), pl.cdiv(t, bk)
    grid = (bh, nq, nk)
    kernel = functools.partial(_fwd_kernel, scale=scale, bq=bq, bk=bk,
                               causal=causal,
                               sched=causal_schedule(t, bq, bk, d))
    with jax.named_scope("flash_fwd"):
        out, lse = pl.pallas_call(
            kernel,
            name="flash_fwd",
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, 8, bq), lambda b, i, j: (b, 0, i)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, t, d), q.dtype),
                jax.ShapeDtypeStruct((bh, 8, t), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, _LANES), jnp.float32),  # running max
                pltpu.VMEM((bq, _LANES), jnp.float32),  # running denominator
                pltpu.VMEM((bq, d), jnp.float32),       # output accumulator
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(q, k, v)
    return out, lse


# -------------------------------------------------------------- backward
def _p_ds(q, k, v, do, lse, delta, scale, mask):
    """P and dS = P * (dO V^T - delta) of the rows ``q`` against the keys
    ``k``, P recomputed from the rows' saved log-sum-exp."""
    p = jnp.exp(_scores(q, k, scale, mask) - lse)           # (rows, keys)
    return p, p * (_dot(do, v, _NT) - delta)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr,
                *, scale, bq, bk, causal, sched):
    ki, qi = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def tile(r, c, mask):
        q, do = q_ref[0, r, :], do_ref[0, r, :]
        p, ds = _p_ds(q, k_ref[0, c, :], v_ref[0, c, :], do,
                      _column(lse_ref, r), _column(delta_ref, r), scale,
                      mask)
        dv_scr[c, :] += _dot(p.astype(do.dtype), do, _TN)   # P^T @ dO
        dk_scr[c, :] += scale * _dot(ds.astype(q.dtype), q, _TN)

    _over_block(causal, sched, qi, ki, bq, bk, tile)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_scr, *, scale, bq, bk, causal, sched):
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def tile(r, c, mask):
        k = k_ref[0, c, :]
        _, ds = _p_ds(q_ref[0, r, :], k, v_ref[0, c, :], do_ref[0, r, :],
                      _column(lse_ref, r), _column(delta_ref, r), scale,
                      mask)
        dq_scr[r, :] += scale * _dot(ds.astype(k.dtype), k, _NN)  # dS @ K

    _over_block(causal, sched, qi, ki, bq, bk, tile)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "bq", "bk", "causal",
                                             "interpret"))
def _flash_backward(res, g, dlse=None, *, scale, bq, bk, causal,
                    interpret):
    q, k, v, out, lse = res
    do = g
    bh, t, d = q.shape
    sched = causal_schedule(t, bq, bk, d)
    # delta_i = rowsum(dO_i * O_i) — cheap, fused by XLA.
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                                  # (bh, t)
    if dlse is not None:
        # Cotangent flowing into the exposed log-sum-exp output (ring
        # attention's merge weights): d(lse_i)/d(s_ij) = p_ij, so the
        # per-row dlse term enters ds = p*(dp - delta + dlse) — i.e.
        # exactly like delta with opposite sign.  Fold it in here so
        # the two backward kernels need no changes.
        delta = delta - dlse.astype(jnp.float32)
    delta = jnp.broadcast_to(delta[:, None, :], lse.shape)    # (bh, 8, t)
    nq, nk = pl.cdiv(t, bq), pl.cdiv(t, bk)

    with jax.named_scope("flash_dkv"):
        dk, dv = pl.pallas_call(
            functools.partial(_dkv_kernel, scale=scale, bq=bq, bk=bk,
                              causal=causal, sched=sched),
            name="flash_dkv",
            grid=(bh, nk, nq),
            in_specs=[
                pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),
                pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
                pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
                pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),
                pl.BlockSpec((1, 8, bq), lambda b, j, i: (b, 0, i)),
                pl.BlockSpec((1, 8, bq), lambda b, j, i: (b, 0, i)),
            ],
            out_specs=[
                pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
                pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, t, d), k.dtype),
                jax.ShapeDtypeStruct((bh, t, d), v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((bk, d), jnp.float32),
                pltpu.VMEM((bk, d), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(q, k, v, do, lse, delta)

    with jax.named_scope("flash_dq"):
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, scale=scale, bq=bq, bk=bk,
                              causal=causal, sched=sched),
            name="flash_dq",
            grid=(bh, nq, nk),
            in_specs=[
                pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, 8, bq), lambda b, i, j: (b, 0, i)),
                pl.BlockSpec((1, 8, bq), lambda b, i, j: (b, 0, i)),
            ],
            out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ------------------------------------------------------------- public op
@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_bhtd(q, k, v, scale, bq, bk, causal, interpret):
    out, _ = _flash_forward(q, k, v, scale=scale, bq=bq, bk=bk,
                            causal=causal, interpret=interpret)
    return out


def _flash_bhtd_fwd(q, k, v, scale, bq, bk, causal, interpret):
    out, lse = _flash_forward(q, k, v, scale=scale, bq=bq, bk=bk,
                              causal=causal, interpret=interpret)
    return out, (q, k, v, out, lse)


def _flash_bhtd_bwd(scale, bq, bk, causal, interpret, res, g):
    return _flash_backward(res, g, scale=scale, bq=bq, bk=bk,
                           causal=causal, interpret=interpret)


_flash_bhtd.defvjp(_flash_bhtd_fwd, _flash_bhtd_bwd)


# ------------------------------------------- partial (lse-exposing) op
# Same kernels, but the row-wise log-sum-exp is a real (differentiable)
# output: ring attention merges per-ring-step partial outputs with
# lse-derived weights (see parallel/ring_attention.py).
@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_bhtd_lse(q, k, v, scale, bq, bk, causal, interpret):
    out, lse = _flash_forward(q, k, v, scale=scale, bq=bq, bk=bk,
                              causal=causal, interpret=interpret)
    return out, lse[:, 0, :]


def _flash_bhtd_lse_fwd(q, k, v, scale, bq, bk, causal, interpret):
    out, lse = _flash_forward(q, k, v, scale=scale, bq=bq, bk=bk,
                              causal=causal, interpret=interpret)
    return (out, lse[:, 0, :]), (q, k, v, out, lse)


def _flash_bhtd_lse_bwd(scale, bq, bk, causal, interpret, res, g):
    do, dlse = g
    return _flash_backward(res, do, scale=scale, bq=bq, bk=bk,
                           causal=causal, interpret=interpret,
                           dlse=dlse)


_flash_bhtd_lse.defvjp(_flash_bhtd_lse_fwd, _flash_bhtd_lse_bwd)


def _folded(q, k, v, causal, block_q, block_k, interpret, scale):
    """What both public ops hand the kernels: [B*H, T, D] operands, the
    clamped blocks, the scale, and the annotation that says, when the
    call is traced, how much of the score square the kernels compute."""
    b, t, h, d = q.shape
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_q = min(block_q, t)
    block_k = min(block_k, t)
    if t % block_q or t % block_k:
        raise ValueError(f"seq len {t} must divide block sizes "
                         f"({block_q}, {block_k})")
    scale = d ** -0.5 if scale is None else float(scale)

    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)

    blocks = (t // block_q) * (t // block_k)
    sched = causal_schedule(t, block_q, block_k, d) if causal \
        else CausalSchedule(0, (), blocks, blocks)
    note = spans.annotate("flash.schedule", t=t, block=block_q,
                          sub=sched.sub, visited=sched.visited,
                          square=sched.square)
    return (fold(q), fold(k), fold(v), scale, block_q, block_k, causal,
            interpret), note


def flash_attention_with_lse(q, k, v, *, causal: bool = True,
                             block_q: int = 256, block_k: int = 256,
                             interpret: bool | None = None,
                             scale: float | None = None):
    """Flash attention that also returns the row log-sum-exp.

    q, k, v: [B, T, H, D] -> (out [B, T, H, D], lse [B, T, H] fp32).
    The lse output is differentiable (its cotangent folds into the
    backward's delta term), which makes this the building block for
    blockwise/ring attention merges."""
    b, t, h, d = q.shape
    args, note = _folded(q, k, v, causal, block_q, block_k, interpret,
                         scale)
    with note:
        out, lse = _flash_bhtd_lse(*args)
    out = out.reshape(b, h, t, d).transpose(0, 2, 1, 3)
    lse = lse.reshape(b, h, t).transpose(0, 2, 1)
    return out, lse


def flash_attention(q, k, v, *, causal: bool = True,
                    block_q: int = 256, block_k: int = 256,
                    interpret: bool | None = None,
                    scale: float | None = None):
    """Causal flash attention.  q, k, v: [B, T, H, D] -> [B, T, H, D];
    ``scale`` multiplies q k^T (None: ``D ** -0.5``).

    ``interpret=None`` auto-selects: the compiled kernel when the
    backend is ``tpu`` (never interpreted there unless a caller asks by
    argument; a kernel that cannot compile raises), the pallas
    interpreter on the CPU backend (so CPU-mesh tests exercise the
    same code).
    Block sizes must keep T % block == 0 (pretraining shapes are
    128-multiples; assert early rather than mask the tail).
    """
    b, t, h, d = q.shape
    args, note = _folded(q, k, v, causal, block_q, block_k, interpret,
                         scale)
    with note:
        out = _flash_bhtd(*args)
    return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)
