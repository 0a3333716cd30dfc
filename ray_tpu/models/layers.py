"""What more than one family's block is built from: RMSNorm, the rotary
position encoding, and the depthwise causal convolution over a window
that a sequence keeps in its slot of the state pool between steps.

``RMSNorm`` and ``_rope`` serve Llama, OLMoE and LFM2 (Granite takes the
norm); ``slot_conv`` is the conv of Granite's ``Mamba2Mixer`` (4 taps,
bias, silu) and of LFM2's ``ShortConvMixer`` (3 taps, neither), written
once; ``init_by_leaf`` makes the seeded weights of all three.
"""

from __future__ import annotations

import dataclasses
import functools
import zlib
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np


@functools.lru_cache(maxsize=64)
def _rope_tables(seq_len: int, head_dim: int, theta: float):
    """Cached sin/cos tables keyed by (seq_len, head_dim): every block
    of every forward shares one host constant per shape instead of
    re-deriving the tables inside each traced layer (they are shape-
    static, so recomputation bought nothing but trace time and
    duplicated constants).  Deliberately NUMPY arrays — caching a
    jnp array materialized under an outer jit would leak that trace's
    tracer into later traces; numpy constants embed safely anywhere.
    Returns ([T, D/2] cos, [T, D/2] sin) in fp32."""
    half = head_dim // 2
    freqs = theta ** (-np.arange(0, half, dtype=np.float32) / half)
    angles = np.arange(seq_len, dtype=np.float32)[:, None] * freqs[None, :]
    return np.cos(angles), np.sin(angles)


def _rope(x, theta: float, positions=None):
    """Rotary embedding over [B, T, H, D] (D even).  ``positions``
    ([B, T] absolute, negative = padding) selects per-token angles for
    the decode path; None means contiguous 0..T-1 (training/prefill
    full forward) served from the cached tables."""
    b, t, h, d = x.shape
    half = d // 2
    if positions is None:
        cos, sin = _rope_tables(t, d, theta)
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:
        pos = jnp.maximum(positions, 0).astype(jnp.float32)
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
        angles = pos[..., None] * freqs            # [B, T, half]
        cos = jnp.cos(angles)[:, :, None, :]
        sin = jnp.sin(angles)[:, :, None, :]
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
