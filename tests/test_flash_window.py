"""The forward-only flash kernel of a serving prefill (interpreted on the
CPU): a sliding window's band against the dense definition at windows
smaller than, equal to and larger than a block, walked in strips and
computed whole, and grouped K/V read where they lie."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import flash_attention
from ray_tpu.ops.flash_attention import band_span


def _dense(q, k, v, window=None, scale=None):
    """float32, K/V repeated to the query heads, ``0 <= i - j < window``."""
    b, t, h, d = q.shape
    rep = h // k.shape[2]
    k, v = (jnp.repeat(x, rep, axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (scale or d ** -0.5)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    mask = i >= j if window is None else (i >= j) & (i - j < window)
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _qkv(t, h, h_kv, d, b=1, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(keys[0], (b, t, h, d), jnp.float32),
            jax.random.normal(keys[1], (b, t, h_kv, d), jnp.float32),
            jax.random.normal(keys[2], (b, t, h_kv, d), jnp.float32))


@pytest.mark.parametrize("window", [1, 5, 16, 24, 32, 48, 64, 200])
def test_band_against_dense_in_small_blocks(window):
    """Blocks of 16 over 64 rows (computed whole under the band's mask):
    windows of a row, inside a block, a block, between blocks, whole
    blocks, the sequence and beyond it."""
    q, k, v = _qkv(64, 4, 4, 16, b=2)
    got = flash_attention(q, k, v, block_q=16, block_k=16, window=window)
    np.testing.assert_allclose(got, _dense(q, k, v, window), atol=2e-5)


@pytest.mark.parametrize("t,window", [(1024, 512), (1536, 1024),
                                      (1024, 768), (512, 2048)])
def test_band_against_dense_in_walked_blocks(t, window):
    """Blocks of 512 are walked in strips of 256: both edge blocks where
    the window is whole blocks, whole and masked where it is not, the
    diagonal alone where the window passes the sequence."""
    q, k, v = _qkv(t, 2, 1, 128)
    got = flash_attention(q, k, v, block_q=512, block_k=512, window=window)
    np.testing.assert_allclose(got, _dense(q, k, v, window), atol=2e-5)


@pytest.mark.parametrize("h,h_kv,d", [(16, 1, 16), (8, 2, 128), (4, 2, 64),
                                      (6, 3, 64), (32, 2, 8)])
@pytest.mark.parametrize("window", [None, 40])
def test_grouped_kv_read_where_they_lie(h, h_kv, d, window):
    """16 query heads a K/V head folded, 4 a head packed at 128 lanes,
    pairs of heads of 64 (repeated inside), an odd count folded."""
    q, k, v = _qkv(64, h, h_kv, d, b=2, seed=1)
    got = flash_attention(q, k, v, block_q=32, block_k=32, window=window)
    np.testing.assert_allclose(got, _dense(q, k, v, window), atol=2e-5)


def test_window_none_is_the_trainers_kernel():
    q, k, v = _qkv(64, 2, 2, 16)
    text = [jax.jit(lambda q, k, v, kw=kw: flash_attention(
        q, k, v, block_q=32, block_k=32, **kw)).lower(q, k, v).as_text()
        for kw in ({}, {"window": None})]
    assert text[0] == text[1]
    banded = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, block_q=32, block_k=32, window=16)).lower(q, k, v).as_text()
    assert banded != text[0]


def test_band_span_counts_the_blocks_that_meet_the_band():
    assert band_span(16384, 1024, 4096) == 5
    assert band_span(16384, 1024, 1024) == 2
    assert band_span(16384, 1024, 1) == 1
    # by hand: row 2048 with window 1025 reaches key 1024, its block's
    # neighbour's first; with 1026 key 1023, one key into a third block
    assert band_span(16384, 1024, 1025) == 2
    assert band_span(16384, 1024, 1026) == 3
    assert band_span(2048, 1024, 4096) == 2     # no more than there are


def test_no_silent_backward_and_no_odd_shapes():
    q, k, v = _qkv(64, 2, 1, 16)
    with pytest.raises(NotImplementedError, match="no backward"):
        jax.grad(lambda q: flash_attention(
            q, k, v, block_q=32, block_k=32, window=8).sum())(q)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, block_q=32, block_k=16, window=8)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, block_q=32, block_k=32, window=8,
                        causal=False)
