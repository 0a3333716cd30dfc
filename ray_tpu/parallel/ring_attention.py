"""Ring attention — context parallelism over the ICI ring.

Fills the reference's sequence-parallel gap (SURVEY.md §5.7: absent
upstream, first-class here).  Sequence is sharded over the ``seq`` mesh
axis; K/V blocks rotate around the ring via ppermute while each device
accumulates online-softmax partial attention for its resident Q block —
blockwise attention in the ring-attention style (Liu et al.), expressed
as a lax.scan inside shard_map so XLA overlaps the permute with compute.

Differentiable by construction (autodiff through scan + ppermute; the
transpose of ppermute is the reverse rotation), with jax.checkpoint on
the per-step body so activation memory stays O(seq_local) per device.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def _block_attn(q, k, v, q_off, kv_off, causal, scale):
    """One (Q_local x KV_block) online-softmax partial.

    q: [B, Tq, H, D]; k, v: [B, Tk, H, D].  Returns (num, den, m) partials
    in fp32: num [B,Tq,H,D], den [B,Tq,H], m [B,Tq,H].
    """
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        q_idx = q_off + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        kv_idx = kv_off + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        mask = q_idx >= kv_idx
        scores = jnp.where(mask[None, None], scores, _NEG_INF)
    m = jnp.max(scores, axis=-1)                      # [B,H,Tq]
    p = jnp.exp(scores - m[..., None])
    p = jnp.where(m[..., None] <= _NEG_INF / 2, 0.0, p)
    den = jnp.sum(p, axis=-1)                          # [B,H,Tq]
    num = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    # Rearrange to [B,Tq,H,...]
    return num, den.transpose(0, 2, 1), m.transpose(0, 2, 1)


def _merge(num, den, m, num2, den2, m2):
    m_new = jnp.maximum(m, m2)
    a1 = jnp.exp(m - m_new)
    a2 = jnp.exp(m2 - m_new)
    num = num * a1[..., None] + num2 * a2[..., None]
    den = den * a1 + den2 * a2
    return num, den, m_new


def ring_attention(q, k, v, *, axis_name: str = "seq", causal: bool = True,
                   scale: Optional[float] = None,
                   checkpoint_steps: Optional[bool] = None,
                   impl: str = "flash",
                   block_q: int = 256, block_k: int = 256):
    """Attention over a sequence sharded on ``axis_name``.

    Must be called inside shard_map (or pmap) with q/k/v local shards of
    shape [batch, seq_local, heads, head_dim].  Returns the local output
    shard, same shape/dtype as q.

    ``impl="flash"`` (default) computes each ring step's blockwise
    attention with the Pallas flash kernel (ops/flash_attention.py) and
    merges normalized partials by log-sum-exp weights, so long-context
    SP runs at flash throughput; the ppermute of the next K/V block is
    issued before the step's kernel, letting XLA overlap the ICI
    transfer with MXU compute.  ``impl="lax"`` keeps the plain-lax
    online-softmax path (reference semantics / debugging).

    ``checkpoint_steps`` defaults per impl: False for flash (the
    kernel's custom vjp already keeps only O(seq_local) residuals per
    step — k/v blocks, partial out, lse — so remat would just rerun
    the forward kernel in the backward for nothing) and True for lax
    (whose step materializes [Tq, Tk] score blocks).
    """
    if impl == "flash":
        if checkpoint_steps is None:
            checkpoint_steps = False
        return _ring_attention_flash(q, k, v, axis_name=axis_name,
                                     causal=causal, scale=scale,
                                     checkpoint_steps=checkpoint_steps,
                                     block_q=block_q, block_k=block_k)
    if checkpoint_steps is None:
        checkpoint_steps = True
    n = jax.lax.psum(1, axis_name)
    rank = jax.lax.axis_index(axis_name)
    b, t_local, h, d = q.shape
    if scale is None:
        scale = d ** -0.5
    q32 = q.astype(jnp.float32)

    def step(carry, i):
        kv, num, den, m = carry
        k_blk, v_blk = kv
        src = (rank - i) % n      # whose block we currently hold
        num2, den2, m2 = _block_attn(
            q32, k_blk.astype(jnp.float32), v_blk.astype(jnp.float32),
            q_off=rank * t_local, kv_off=src * t_local,
            causal=causal, scale=scale)
        num, den, m = _merge(num, den, m, num2, den2, m2)
        # Rotate K/V to the next device (i -> i+1 around the ring).
        perm = [(j, (j + 1) % n) for j in range(n)]
        kv = jax.tree_util.tree_map(
            lambda x: jax.lax.ppermute(x, axis_name, perm), (k_blk, v_blk))
        return (kv, num, den, m), None

    if checkpoint_steps:
        step = jax.checkpoint(step)

    num0 = jnp.zeros((b, t_local, h, d), jnp.float32)
    den0 = jnp.zeros((b, t_local, h), jnp.float32)
    m0 = jnp.full((b, t_local, h), _NEG_INF, jnp.float32)
    (_, num, den, m), _ = jax.lax.scan(
        step, ((k, v), num0, den0, m0), jnp.arange(n))
    den = jnp.where(den == 0.0, 1.0, den)
    out = num / den[..., None]
    return out.astype(q.dtype)


def _ring_attention_flash(q, k, v, *, axis_name: str, causal: bool,
                          scale: Optional[float],
                          checkpoint_steps: bool,
                          block_q: int, block_k: int):
    """Flash-kernel ring attention (round-2 VERDICT item 3).

    Each ring step runs the Pallas kernel on (Q_local, KV_block) and
    merges NORMALIZED partial outputs with their log-sum-exps:
        lse' = logaddexp(lse_acc, lse_blk)
        out' = out_acc*exp(lse_acc-lse') + out_blk*exp(lse_blk-lse')
    Block-level causality is decided per step (src ring position vs our
    rank): blocks strictly before us are dense, our own block is
    in-kernel causal, blocks after us are skipped — a lax.switch, so
    the skipped branch costs nothing on device.
    """
    from ..ops.flash_attention import flash_attention_with_lse

    n = jax.lax.psum(1, axis_name)
    rank = jax.lax.axis_index(axis_name)
    b, t_local, h, d = q.shape
    if scale is not None and abs(scale - d ** -0.5) > 1e-9:
        raise ValueError("flash impl uses the standard 1/sqrt(d) scale")

    def partial_flash(k_blk, v_blk, blk_causal: bool):
        out, lse = flash_attention_with_lse(
            q, k_blk, v_blk, causal=blk_causal,
            block_q=block_q, block_k=block_k)
        return out.astype(jnp.float32), lse

    def step(carry, i):
        (k_blk, v_blk), out_acc, lse_acc = carry
        # Issue the rotation FIRST so the ICI transfer of the next K/V
        # block overlaps this step's kernel (scan keeps the data
        # dependency: the permuted block is only consumed next step).
        perm = [(j, (j + 1) % n) for j in range(n)]
        kv_next = jax.tree_util.tree_map(
            lambda x: jax.lax.ppermute(x, axis_name, perm),
            (k_blk, v_blk))
        src = (rank - i) % n      # whose block we currently hold

        def merge(args):
            out_blk, lse_blk = args
            lse_new = jnp.logaddexp(lse_acc, lse_blk)
            w1 = jnp.exp(lse_acc - lse_new)
            w2 = jnp.exp(lse_blk - lse_new)
            return (out_acc * w1[..., None] + out_blk * w2[..., None],
                    lse_new)

        def do_dense(_):
            return merge(partial_flash(k_blk, v_blk, False))

        def do_diag(_):
            return merge(partial_flash(k_blk, v_blk, causal))

        def do_skip(_):
            return out_acc, lse_acc

        if causal:
            case = jnp.where(src == rank, 1,
                             jnp.where(src < rank, 0, 2))
            out_acc, lse_acc = jax.lax.switch(
                case, [do_dense, do_diag, do_skip], None)
        else:
            out_acc, lse_acc = do_dense(None)
        return (kv_next, out_acc, lse_acc), None

    if checkpoint_steps:
        step = jax.checkpoint(step)

    out0 = jnp.zeros((b, t_local, h, d), jnp.float32)
    lse0 = jnp.full((b, t_local, h), _NEG_INF, jnp.float32)
    (_, out, _), _ = jax.lax.scan(step, ((k, v), out0, lse0),
                                  jnp.arange(n))
    return out.astype(q.dtype)
