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
accumulator in VMEM scratch.  Causal blocks above the diagonal are
skipped (predicated off), the diagonal block is masked in-register.

Backward: custom_vjp with the standard two-kernel flash backward — a
dkv kernel (grid over kv blocks, scanning q) and a dq kernel (grid over
q blocks, scanning kv), both recomputing P from the saved row-wise
log-sum-exp instead of reading a stored score matrix.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _causal_mask(qi, ki, bq, bk):
    """(bq, bk) bool mask for the (qi, ki) block pair: row >= col."""
    rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return rows >= cols


# --------------------------------------------------------------- forward
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, bq, bk, causal):
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Causal: skip kv blocks strictly above the diagonal.
    visit = (ki * bk <= qi * bq + bq - 1) if causal else True

    @pl.when(visit)
    def _compute():
        q = q_ref[0]                      # (bq, d) bf16
        k = k_ref[0]                      # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (bq, bk)
        if causal:
            s = jnp.where(_causal_mask(qi, ki, bq, bk), s, _NEG_INF)
        m_prev = m_scr[:, :1]                               # (bq, 1)
        row_max = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, row_max)
        p = jnp.exp(s - m_new)                              # (bq, bk) f32
        alpha = jnp.exp(m_prev - m_new)                     # (bq, 1)
        l_new = alpha * l_scr[:, :1] + \
            jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

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


def _flash_forward(q, k, v, *, scale, bq, bk, causal, interpret):
    bh, t, d = q.shape
    nq, nk = pl.cdiv(t, bq), pl.cdiv(t, bk)
    grid = (bh, nq, nk)
    kernel = functools.partial(_fwd_kernel, scale=scale, bq=bq, bk=bk,
                               causal=causal)
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
                pltpu.VMEM((bq, 128), jnp.float32),   # running max
                pltpu.VMEM((bq, 128), jnp.float32),   # running denominator
                pltpu.VMEM((bq, d), jnp.float32),     # output accumulator
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(q, k, v)
    return out, lse


# -------------------------------------------------------------- backward
def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr,
                *, scale, bq, bk, causal):
    ki, qi = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    visit = (qi * bq + bq - 1 >= ki * bk) if causal else True

    @pl.when(visit)
    def _compute():
        q = q_ref[0]                      # (bq, d)
        k = k_ref[0]                      # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (bq, bk)
        if causal:
            s = jnp.where(_causal_mask(qi, ki, bq, bk), s, _NEG_INF)
        lse = lse_ref[0, :1, :].reshape(-1, 1)               # (bq, 1)
        p = jnp.exp(s - lse)                                 # (bq, bk)
        do = do_ref[0]                                       # (bq, d)
        # dv += P^T @ dO
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dS = P * (dO @ V^T - delta)
        dp = jax.lax.dot_general(
            do, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # (bq, bk)
        delta = delta_ref[0, :1, :].reshape(-1, 1)           # (bq, 1)
        ds = p * (dp - delta)                                # (bq, bk)
        # dK += dS^T @ Q * scale
        dk_scr[...] += scale * jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_scr, *, scale, bq, bk, causal):
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    visit = (ki * bk <= qi * bq + bq - 1) if causal else True

    @pl.when(visit)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s = jnp.where(_causal_mask(qi, ki, bq, bk), s, _NEG_INF)
        lse = lse_ref[0, :1, :].reshape(-1, 1)
        p = jnp.exp(s - lse)
        do = do_ref[0]
        dp = jax.lax.dot_general(
            do, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        delta = delta_ref[0, :1, :].reshape(-1, 1)
        ds = p * (dp - delta)
        dq_scr[...] += scale * jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _flash_backward(res, g, *, scale, bq, bk, causal, interpret,
                    dlse=None):
    q, k, v, out, lse = res
    do = g
    bh, t, d = q.shape
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
                              causal=causal),
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
                              causal=causal),
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

    out, lse = _flash_bhtd_lse(fold(q), fold(k), fold(v), scale,
                               block_q, block_k, causal, interpret)
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

    out = _flash_bhtd(fold(q), fold(k), fold(v), scale, block_q, block_k,
                      causal, interpret)
    return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)
