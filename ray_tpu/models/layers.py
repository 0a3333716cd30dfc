"""What more than one family's block is built from: RMSNorm, the rotary
position encoding, and the depthwise causal convolution over a window
that a sequence keeps in its slot of the state pool between steps.

``RMSNorm`` and ``_rope`` serve Llama, OLMoE, LFM2 and Kimi-K2 (Granite
takes the norm; Kimi-K2 rotates a 64-wide PART of a head, with YaRN's
frequencies: ``yarn_inv_freq``, ``yarn_mscale``; Command A+ turns
adjacent pairs, ``interleaved``, and norms with ``LayerNorm``, which
takes the mean out); ``slot_conv`` is the conv of Granite's ``Mamba2Mixer`` (4 taps,
bias, silu) and of LFM2's ``ShortConvMixer`` (3 taps, neither), written
once; ``init_by_leaf`` makes the seeded weights of all three.
``served_position`` is what GPT-2 and the decoder cut their hidden state
to, before the final norm and the head, for a caller that serves one
position of each row.  ``chunked_xent`` is the head's loss without the
[B, T, V] logits (one scan makes each chunk's logits once and their
gradient with them), ``chunked_xent_over`` the same with the tokens cut
over a mesh, and ``xent_layout`` says which a batch takes: GPT-2 trains
through them (``gpt2.loss_layout``), the decoder's loss does not yet.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import zlib
from typing import Any, Callable, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.sharding import logical_spec


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature: ``0.1 * mscale * ln(factor) + 1``
    (1 where nothing is stretched)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


@functools.lru_cache(maxsize=16)
def yarn_inv_freq(dim: int, theta: float, factor: float,
                  original_max: int, beta_fast: float,
                  beta_slow: float) -> np.ndarray:
    """YaRN's inverse frequencies for a rotary part of ``dim`` numbers,
    [dim/2] float32.  With ``f_i = theta ** (-2i / dim)``: a dimension
    that turns more than ``beta_fast`` times over the ``original_max``
    positions keeps ``f_i`` (extrapolated), one that turns fewer than
    ``beta_slow`` times gets ``f_i / factor`` (interpolated), and between
    the two correction dimensions ``c(rot) = dim * ln(original_max /
    (2 pi rot)) / (2 ln theta)`` (floor of the fast one, ceiling of the
    slow one, clipped to 0 .. dim-1) a linear ramp mixes them."""
    def correction(rotations: float) -> float:
        return dim * math.log(original_max / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    half = dim // 2
    f = theta ** (-np.arange(half, dtype=np.float32) / half)
    ramp = np.clip((np.arange(half, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)   # 0: extrapolated, 1: not
    return (f / factor * ramp + f * (1.0 - ramp)).astype(np.float32)


@functools.lru_cache(maxsize=64)
def _rope_tables(seq_len: int, head_dim: int, theta: float, yarn=None):
    """Cached sin/cos tables keyed by (seq_len, head_dim): every block
    of every forward shares one host constant per shape instead of
    re-deriving the tables inside each traced layer (they are shape-
    static, so recomputation bought nothing but trace time and
    duplicated constants).  Deliberately NUMPY arrays — caching a
    jnp array materialized under an outer jit would leak that trace's
    tracer into later traces; numpy constants embed safely anywhere.
    Returns ([T, D/2] cos, [T, D/2] sin) in fp32.  ``yarn``: the
    arguments of ``yarn_inv_freq`` after ``theta``, where the
    frequencies are YaRN's."""
    half = head_dim // 2
    freqs = theta ** (-np.arange(0, half, dtype=np.float32) / half) \
        if yarn is None else yarn_inv_freq(head_dim, theta, *yarn)
    angles = np.arange(seq_len, dtype=np.float32)[:, None] * freqs[None, :]
    return np.cos(angles), np.sin(angles)


def _rope(x, theta: float, positions=None, yarn=None,
          interleaved: bool = False):
    """Rotary embedding over [B, T, H, D] (D even; rotate-half: dimension
    i turns with dimension i + D/2; ``interleaved``, the source's
    ``rope_gptj``: dimension 2i with 2i + 1, at the same frequency
    ``theta ** (-2i / D)``).  ``positions`` ([B, T] absolute,
    negative = padding) selects per-token angles for the decode path;
    None means contiguous 0..T-1 (training/prefill full forward) served
    from the cached tables.  ``yarn`` ((factor, original_max, beta_fast,
    beta_slow), hashable): YaRN's frequencies in place of ``theta **
    (-2i/D)``."""
    b, t, h, d = x.shape
    half = d // 2
    if positions is None:
        cos, sin = _rope_tables(t, d, theta, yarn)
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:
        pos = jnp.maximum(positions, 0).astype(jnp.float32)
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half) \
            if yarn is None else jnp.asarray(yarn_inv_freq(d, theta, *yarn))
        angles = pos[..., None] * freqs            # [B, T, half]
        cos = jnp.cos(angles)[:, :, None, :]
        sin = jnp.sin(angles)[:, :, None, :]
    if interleaved:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                        axis=-1).reshape(x.shape)
        return out.astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1)
    return out.astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        x32 = x.astype(jnp.float32)
        norm = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (norm * scale).astype(self.dtype)


class LayerNorm(nn.Module):
    """``(x - mean) / sqrt(var + eps) * scale`` in float32: the mean taken
    out, where ``RMSNorm`` leaves it in; no bias (Cohere's)."""
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        x32 = x.astype(jnp.float32)
        x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
        norm = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (norm * scale).astype(self.dtype)


def served_position(x, last):
    """x [B, T, ...] -> [B, 1, ...]: position ``last[b]`` (int32 [B], an
    index within T) of row b.  Taken as B rows of ``x`` flattened to
    [B*T, ...], the shape the compiled program gives every matmul's
    operand: cut along the sequence of the [B, T, d] form, the TPU
    compiler hands each sparse layer's output on a second time in that
    form (Kimi-K2.5's ``prefill[4096]``: ten copies of an activation and
    0.7 GB of temporaries more than with every row's logits)."""
    b, t = x.shape[:2]
    rows = x.reshape((b * t,) + x.shape[2:])[last + t * jnp.arange(b)]
    return rows[:, None]


def slot_conv(x, taps, window=None, bias=None,
              act: Optional[Callable] = None):
    """Depthwise causal convolution of x [B, T, C] with ``taps`` [K, C]:
    ``out_t = act(sum_i taps[i] x_{t-K+1+i} + bias)``, in float32, over
    each row's own past.

    Without a ``window`` (training, the full forward) the past before
    position 0 is zeros.  With one, ``(pool, layer, slots, fresh,
    valid)`` (the WHOLE pool [layers, slots, K-1, C]; this mixer's layer
    in it; each row's slot [B]; whether the row's first position is 0
    [B]; which positions are real, [B, T]: a position < 0 is padding),
    the last K-1 inputs of the row's past are read from its slot, and
    the window the NEXT position needs is written there: the K-1 inputs
    up to the last real one (a prefill's padding lies behind the real
    positions and is left out).  A ``fresh`` row starts from zeros
    whatever its slot holds: that is how a slot is cleared for the
    sequence that takes it.  A row whose slot index lies outside the
    pool (a padded decode row) changes nothing: its window is dropped.

    Returns (out [B, T, C] float32, the pool updated or None)."""
    b, t, c = x.shape
    kw = taps.shape[0]
    f32 = jnp.float32
    if window is None:
        before = jnp.zeros((b, kw - 1, c), x.dtype)
    else:       # the last kw-1 inputs of the row's past
        # (the pool through a view with the window's two dimensions
        # merged: written in four dimensions the compiler re-lays the
        # POOL for its update, models/granite.py _store_rows)
        pool, layer, slots, fresh, valid = window
        flat = pool.reshape(pool.shape[:2] + (-1,))
        before = jnp.where(fresh[:, None, None], 0,
                           flat[layer, slots].reshape(b, kw - 1, c))
    past = jnp.concatenate([before.astype(x.dtype), x], axis=1)
    out = sum(past[:, i:i + t].astype(f32) * taps[i] for i in range(kw))
    if bias is not None:
        out = out + bias
    if act is not None:
        out = act(out)
    if window is None:
        return out, None
    n_real = jnp.sum(valid, axis=1)                            # [B]
    keep = jax.vmap(lambda w, i: jax.lax.dynamic_slice_in_dim(
        w, i, kw - 1, axis=0))(past, n_real)
    pool = flat.at[layer, slots].set(
        keep.reshape(b, -1).astype(flat.dtype),
        mode="drop").reshape(pool.shape)
    return out, pool


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _init_leaf(key, shape, ones: bool, dtype):
    x = jnp.ones(shape, jnp.float32) if ones \
        else 0.02 * jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


def init_by_leaf(model, cfg, rng, special: Optional[Callable] = None):
    """The weights of ``model(cfg)`` from the seed, leaf by leaf: shapes
    by ``eval_shape`` (the forward is never run to make weights); each
    leaf drawn in float32 from a key folded from its path (normal, std
    0.02; a norm's ``scale`` is 1) and cast to ``cfg.param_dtype`` under
    ``jit``, so no float32 copy of the whole tree ever exists, on any
    backend.  ``special(name, key, shape)`` may give a leaf of its own
    (None: the default draw)."""
    init_cfg = dataclasses.replace(cfg, mesh=None, attn_impl="dense")
    shapes = jax.eval_shape(model(init_cfg).init, rng,
                            jnp.zeros((1, min(cfg.max_seq, 8)), jnp.int32))

    def make(path, spec):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        key = jax.random.fold_in(rng, zlib.crc32(name.encode()))
        leaf = special(name, key, spec.shape) if special else None
        if leaf is not None:
            return leaf
        return _init_leaf(key, spec.shape, name.endswith("scale"),
                          jnp.dtype(cfg.param_dtype))

    return jax.tree_util.tree_map_with_path(make, shapes)


# ---------------------------------------------------------------------
# The chunked cross entropy: the loss of a tied or untied head without the
# [B, T, V] logits, on one chip or with the tokens split over a mesh.

def _xent_chunks(x, targets, chunk: int):
    """``x`` [b,t,d] and ``targets`` [b,t] as the scan reads them:
    [n,b,c,d] and [n,b,c], ``n = t // chunk`` chunks along the sequence."""
    b, t, d = x.shape
    n = t // chunk
    return (jnp.moveaxis(x.reshape(b, n, chunk, d), 1, 0),
            jnp.moveaxis(targets.reshape(b, n, chunk), 1, 0))


def _xent_chunk(xc, wte, tc):
    """One chunk's float32 logits, their log-sum-exp and the sum of the
    rows' losses."""
    logits = jnp.einsum("bcd,vd->bcv", xc, wte,
                        preferred_element_type=jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)                  # [b,c]
    tgt = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
    return logits, lse, jnp.sum(lse - tgt)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def chunked_xent(x, wte, targets, chunk: int) -> jnp.ndarray:
    """Fused chunked cross entropy (custom_vjp): never materializes the
    [B, T, V] logits tensor in HBM, and makes each chunk's logits ONCE.

    The fp32 logits (~3.3 GB at GPT-2 pretraining shapes, several HBM
    round-trips through log_softmax and its VJP) would be the biggest
    memory consumer of the step; a scan over seq chunks keeps the live
    slab at O(chunk*V).  The loss is the last thing the forward pass
    computes and its value is a scalar, so its cotangent only SCALES
    the gradient: under differentiation the one scan that makes a
    chunk's logits also folds their softmax-minus-onehot straight into
    the dX / dWte einsums (three vocabulary-sized matmuls a chunk, none
    recomputed), the residuals are those two gradients, and the
    backward rule multiplies them by the cotangent.  Called without
    differentiation (evaluation) it is the value-only scan: one matmul
    a chunk."""
    def body(total, xt):
        xc, tc = xt
        _logits, _lse, loss = _xent_chunk(xc, wte, tc)
        return total + loss, None

    with jax.named_scope("loss"):
        total, _ = jax.lax.scan(body, jnp.float32(0.0),
                                _xent_chunks(x, targets, chunk))
    b, t, _d = x.shape
    return total / (b * t)


def _chunked_xent_fwd(x, wte, targets, chunk):
    b, t, d = x.shape
    scale = 1.0 / (b * t)

    def body(carry, xt):
        total, dw = carry
        xc, tc = xt
        logits, lse, loss = _xent_chunk(xc, wte, tc)
        p = jnp.exp(logits - lse[..., None])
        onehot = jax.nn.one_hot(tc, wte.shape[0], dtype=p.dtype)
        dl = ((p - onehot) * scale).astype(x.dtype)
        dx_c = jnp.einsum("bcv,vd->bcd", dl, wte)
        # fp32 accumulator: bf16 chunk-wise accumulation would
        # compound rounding across T/chunk scan steps.
        dw = dw + jnp.einsum("bcv,bcd->vd", dl, xc,
                             preferred_element_type=jnp.float32)
        return (total + loss, dw), dx_c

    with jax.named_scope("loss"):
        (total, dw), dxs = jax.lax.scan(
            body, (jnp.float32(0.0), jnp.zeros(wte.shape, jnp.float32)),
            _xent_chunks(x, targets, chunk))
        dx = jnp.moveaxis(dxs, 0, 1).reshape(b, t, d)
        # The empty array carries wte's dtype to the backward rule.
        return total / (b * t), (dx, dw, jnp.zeros((0,), wte.dtype))


def _chunked_xent_bwd(chunk, res, g):
    dx, dw, like_wte = res
    with jax.named_scope("loss"):
        return ((dx * g).astype(dx.dtype),
                (dw * g).astype(like_wte.dtype), None)


chunked_xent.defvjp(_chunked_xent_fwd, _chunked_xent_bwd)


class XentLayout(NamedTuple):
    """How a loss over ``[B, T]`` tokens is computed (``xent_layout``)."""
    path: str = "whole"         # "chunked": the one-scan loss | "whole"
    chunk: int = 0              # positions of a scan step on ONE shard
    rows: Tuple[str, ...] = ()  # mesh axes that cut the batch's rows
    positions: Tuple[str, ...] = ()     # ... each chunk's positions
    shards: int = 1             # into how many shards the tokens are cut


def xent_layout(mesh, shape, chunk: int) -> XentLayout:
    """Which loss ``[B, T] = shape`` tokens take at ``chunk`` positions a
    scan step, by what can be seen: the mesh's axes and the shapes.

    ``T`` is no whole number of chunks (or one at most): whole logits.
    No mesh: chunked, on the one device.  A mesh: the tokens are cut over
    the axes that carry the batch (the table's ``batch`` row fitted to
    ``B``) and then over those of its ``vocab`` row (``tensor``: the axis
    the head's matmul would lie on, which an odd vocabulary leaves with
    the same logits on every shard): the rows a batch shard holds where
    that axis divides them, else the positions of each chunk where it
    divides those, else whole logits, the program GSPMD partitions by
    itself; so too where the sequence is cut (the scan runs along it), or
    where no axis cuts anything."""
    b, t = shape
    if not chunk or t % chunk or t <= chunk:
        return XentLayout()
    if mesh is None:
        return XentLayout("chunked", chunk)
    if _axes(mesh, "seq", t):
        return XentLayout()
    rows, positions = _axes(mesh, "batch", b), ()
    head = tuple(a for a in _axes(mesh, "vocab") if a not in rows)
    if head and b // _shards(mesh, rows) % _shards(mesh, head) == 0:
        rows += head
    elif head and chunk % _shards(mesh, head) == 0:
        positions = head
    elif head or not rows:
        return XentLayout()
    return XentLayout("chunked", chunk // _shards(mesh, positions), rows,
                      positions, _shards(mesh, rows + positions))


def _axes(mesh, name: str, size: Optional[int] = None) -> Tuple[str, ...]:
    """The axes of ``mesh`` on which the table lays a dimension with the
    logical name ``name`` (with ``size``: those that divide it)."""
    spec = logical_spec(mesh, (name,), None if size is None else (size,))
    entry = spec[0] if len(spec) else None      # pruned: no entry left
    if entry is None:
        return ()
    return (entry,) if isinstance(entry, str) else tuple(entry)


def _shards(mesh, axes) -> int:
    return math.prod(mesh.shape[a] for a in axes)


def chunked_xent_over(mesh, layout: XentLayout, x, wte, targets):
    """``chunked_xent`` with the tokens cut as ``layout`` says: every
    shard scans its own rows (or its own positions of each chunk)
    against the whole ``wte`` and the shards' mean losses are summed, so
    no shard makes another's logits.  Differentiated, ``shard_map``'s
    transpose sums ``d wte`` over the mesh ONCE, after the scan (the
    scan's accumulator is a shard's own partial sum), and leaves ``dx``
    cut by tokens."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    b, t, d = x.shape
    axes = layout.rows + layout.positions
    n = t // (layout.chunk * _shards(mesh, layout.positions))
    spec = P(layout.rows or None, None, layout.positions or None)

    def shard(x, wte, targets):
        rows = x.shape[0]
        loss = chunked_xent(x.reshape(rows, -1, d), wte,
                            targets.reshape(rows, -1), layout.chunk)
        return jax.lax.psum(loss, axes) / layout.shards

    return shard_map(shard, mesh=mesh, check_vma=False,
                     in_specs=(spec, P(), spec), out_specs=P())(
        x.reshape(b, n, -1, d), wte, targets.reshape(b, n, -1))
