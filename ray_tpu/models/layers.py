"""What more than one family's block is built from: RMSNorm, the rotary
position encoding, and the depthwise causal convolution over a window
that a sequence keeps in its slot of the state pool between steps.

``RMSNorm`` and ``_rope`` serve Llama, OLMoE, LFM2 and Kimi-K2 (Granite
takes the norm; Kimi-K2 rotates a 64-wide PART of a head, with YaRN's
frequencies: ``yarn_inv_freq``, ``yarn_mscale``); ``slot_conv`` is the conv of Granite's ``Mamba2Mixer`` (4 taps,
bias, silu) and of LFM2's ``ShortConvMixer`` (3 taps, neither), written
once; ``init_by_leaf`` makes the seeded weights of all three.
``served_position`` is what GPT-2 and the decoder cut their hidden state
to, before the final norm and the head, for a caller that serves one
position of each row.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import zlib
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np


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


def _rope(x, theta: float, positions=None, yarn=None):
    """Rotary embedding over [B, T, H, D] (D even; rotate-half: dimension
    i turns with dimension i + D/2).  ``positions`` ([B, T] absolute,
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
