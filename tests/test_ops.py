"""Pallas kernel correctness (runs in interpreter mode on the CPU mesh;
the same code path compiles on TPU — block sizes and layouts identical).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import flash_attention


def _dense_ref(q, k, v, causal=True):
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * (q.shape[-1] ** -0.5)
    if causal:
        t = q.shape[1]
        mask = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0) >= \
            jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
        scores = jnp.where(mask[None, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))


def _rand_qkv(b=2, t=256, h=4, d=64, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    return tuple(jax.random.normal(k, (b, t, h, d), dtype) for k in ks)


def test_flash_forward_matches_dense():
    q, k, v = _rand_qkv()
    out = flash_attention(q, k, v, block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_dense_ref(q, k, v)),
                               atol=2e-5, rtol=2e-5)


def test_flash_forward_non_causal():
    q, k, v = _rand_qkv()
    out = flash_attention(q, k, v, causal=False, block_q=128, block_k=128)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_dense_ref(q, k, v, causal=False)),
        atol=2e-5, rtol=2e-5)


def test_flash_gradients_match_dense():
    q, k, v = _rand_qkv()

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, block_q=128,
                                       block_k=128) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_dense_ref(q, k, v) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        scale = float(jnp.max(jnp.abs(b))) + 1e-9
        assert float(jnp.max(jnp.abs(a - b))) / scale < 1e-4


def test_flash_whole_sequence_block():
    """The flagship config: block == seq (fully fused, no streaming)."""
    q, k, v = _rand_qkv(t=256)
    out = flash_attention(q, k, v, block_q=1024, block_k=1024)  # clamped
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_dense_ref(q, k, v)),
                               atol=2e-5, rtol=2e-5)


def test_flash_rejects_indivisible_seq():
    q, k, v = _rand_qkv(t=200)
    with pytest.raises(ValueError, match="divide"):
        flash_attention(q, k, v, block_q=128, block_k=128)


def test_flash_causality_is_exact():
    """Future tokens must not leak: perturbing k/v at position j > i
    cannot change output at i."""
    q, k, v = _rand_qkv(t=128)
    out1 = flash_attention(q, k, v, block_q=128, block_k=128)
    k2 = k.at[:, 100:].set(99.0)
    v2 = v.at[:, 100:].set(-99.0)
    out2 = flash_attention(q, k2, v2, block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(out1[:, :100]),
                               np.asarray(out2[:, :100]),
                               atol=1e-6)


def test_chunked_xent_matches_plain():
    from ray_tpu.models.gpt2 import GPT2Config, gpt2_init, gpt2_loss_fn

    cfg = GPT2Config(vocab_size=256, n_layer=1, n_head=4, d_model=128,
                     d_ff=256, max_seq=256, remat=False)
    params = gpt2_init(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 257), 0, 256,
                              jnp.int32)
    plain = gpt2_loss_fn(cfg, params, {"tokens": toks}, loss_chunk=0)
    chunked = gpt2_loss_fn(cfg, params, {"tokens": toks}, loss_chunk=128)
    assert abs(float(plain) - float(chunked)) < 1e-4
    # Gradients agree to bf16/fp32 einsum-ordering precision: the
    # fused custom_vjp backward recomputes logits chunk-wise and folds
    # softmax-minus-onehot into the grad einsums, so per-element
    # rounding differs from the autodiff whole-logits path (measured
    # <=0.2% of the peak gradient magnitude).
    g1 = jax.grad(lambda p: gpt2_loss_fn(cfg, p, {"tokens": toks},
                                         loss_chunk=0))(params)
    g2 = jax.grad(lambda p: gpt2_loss_fn(cfg, p, {"tokens": toks},
                                         loss_chunk=128))(params)
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g2)):
        err = float(jnp.max(jnp.abs(a - b)))
        peak = float(jnp.max(jnp.abs(a))) + 1e-12
        assert err < max(5e-4, 2e-2 * peak), (err, peak)


def test_gpt2_flash_attn_impl():
    """Model-level: attn_impl='flash' trains a step on the CPU mesh."""
    from ray_tpu.models.gpt2 import GPT2Config, gpt2_init, gpt2_loss_fn

    cfg = GPT2Config(vocab_size=256, n_layer=2, n_head=4, d_model=128,
                     d_ff=256, max_seq=128, attn_impl="flash", remat=False)
    params = gpt2_init(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 129), 0, 256,
                              jnp.int32)
    loss, grads = jax.value_and_grad(
        lambda p: gpt2_loss_fn(cfg, p, {"tokens": toks}))(params)
    assert jnp.isfinite(loss)
    flat = jax.tree_util.tree_leaves(grads)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in flat)

    # flash must agree with dense at the loss level
    cfg_d = GPT2Config(vocab_size=256, n_layer=2, n_head=4, d_model=128,
                       d_ff=256, max_seq=128, attn_impl="dense",
                       remat=False)
    loss_d = gpt2_loss_fn(cfg_d, params, {"tokens": toks})
    assert abs(float(loss) - float(loss_d)) < 1e-2
