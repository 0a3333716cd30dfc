"""Pallas kernel correctness (runs in interpreter mode on the CPU mesh;
the same code path compiles on TPU — block sizes and layouts identical).
"""

import contextlib
import functools

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


def _dense_scaled(q, k, v, causal=True, scale=None):
    """The dense definition with a scale given (None: 1/sqrt(d))."""
    if scale is None:
        return _dense_ref(q, k, v, causal)
    return _dense_ref(q * (scale * q.shape[-1] ** 0.5), k, v, causal)


# The walk inside a diagonal grid block (PR 34): a 1024 block at T = 1024
# is the benchmark cells' own call; T = 2048 has a diagonal and an
# off-diagonal grid block; 512 is the smallest block that is walked;
# Granite's call gives a scale; non-causal computes every block whole.
WALK_CASES = [
    pytest.param(1024, 1024, 64, None, True, id="t1024_blk1024_d64"),
    pytest.param(1024, 1024, 128, None, True, id="t1024_blk1024_d128"),
    pytest.param(2048, 1024, 64, None, True, id="t2048_blk1024_d64"),
    pytest.param(1024, 512, 64, None, True, id="t1024_blk512_d64"),
    pytest.param(1024, 512, 128, 1 / 128, True,
                 id="t1024_blk512_d128_scale"),
    pytest.param(1024, 1024, 64, None, False, id="t1024_blk1024_noncausal"),
]


@pytest.mark.parametrize("t,block,d,scale,causal", WALK_CASES)
def test_flash_walk_forward_matches_dense(t, block, d, scale, causal):
    q, k, v = _rand_qkv(b=1, t=t, h=2, d=d)
    out = flash_attention(q, k, v, causal=causal, block_q=block,
                          block_k=block, scale=scale)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(_dense_scaled(q, k, v, causal, scale)),
        atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("t,block,d,scale,causal", WALK_CASES)
def test_flash_walk_gradients_match_dense(t, block, d, scale, causal):
    q, k, v = _rand_qkv(b=1, t=t, h=2, d=d)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       block_q=block, block_k=block,
                                       scale=scale) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_dense_scaled(q, k, v, causal, scale) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        scale_ = float(jnp.max(jnp.abs(b))) + 1e-9
        assert float(jnp.max(jnp.abs(a - b))) / scale_ < 1e-4


def test_flash_with_lse_cotangent_on_a_walked_block():
    """The lse output and its cotangent (ring attention's merge weights)
    through a block that is walked in sub-blocks."""
    from ray_tpu.ops.flash_attention import flash_attention_with_lse

    q, k, v = _rand_qkv(b=1, t=512, h=2)

    def ref(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((512, 512), bool)), s, -1e30)
        return (_dense_ref(q, k, v),
                jax.nn.logsumexp(s, axis=-1).transpose(0, 2, 1))

    def loss(f):
        def fn(q, k, v):
            out, lse = f(q, k, v)
            return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))
        return fn

    flash = functools.partial(flash_attention_with_lse, block_q=512,
                              block_k=512)
    out, lse = flash(q, k, v)
    out_r, lse_r = ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_r),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_r),
                               atol=2e-5, rtol=2e-5)
    g1 = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        scale = float(jnp.max(jnp.abs(b))) + 1e-9
        assert float(jnp.max(jnp.abs(a - b))) / scale < 1e-4


def test_flash_rejects_indivisible_seq():
    q, k, v = _rand_qkv(t=200)
    with pytest.raises(ValueError, match="divide"):
        flash_attention(q, k, v, block_q=128, block_k=128)


@pytest.mark.parametrize("t,block,cut", [(128, 128, 100),
                                         (1024, 1024, 700)],
                         ids=["t128_blk128", "t1024_blk1024_walked"])
def test_flash_causality_is_exact(t, block, cut):
    """Future tokens must not leak: perturbing k/v at position j > i
    cannot change output at i, by one bit where the block is walked
    (an unmasked sub-block pair must lie wholly under the diagonal)."""
    q, k, v = _rand_qkv(b=1, t=t, h=2)
    out1 = flash_attention(q, k, v, block_q=block, block_k=block)
    k2 = k.at[:, cut:].set(99.0)
    v2 = v.at[:, cut:].set(-99.0)
    out2 = flash_attention(q, k2, v2, block_q=block, block_k=block)
    np.testing.assert_array_equal(np.asarray(out1[:, :cut]),
                                  np.asarray(out2[:, :cut]))
    assert not np.array_equal(np.asarray(out1[:, cut:]),
                              np.asarray(out2[:, cut:]))


SCHEDULES = [(1024, 1024, 64), (1024, 1024, 128), (2048, 1024, 64),
             (1024, 512, 64), (8192, 256, 64), (1024, 256, 64),
             (256, 256, 64), (2048, 1024, 128)]


@pytest.mark.parametrize("t,block,d", SCHEDULES,
                         ids=[f"t{t}_blk{b}_d{d}" for t, b, d in SCHEDULES])
def test_causal_schedule_walks_the_triangle_once(t, block, d):
    """Every sub-block pair on or under the diagonal exactly once, none
    above, exactly the diagonal ones masked; the counts are those of the
    whole square."""
    from ray_tpu.ops.flash_attention import causal_schedule

    sched = causal_schedule(t, block, block, d)
    n_grid = t // block
    if block < 512:
        assert sched.sub == 0 and sched.pairs == ()
        assert sched.square == n_grid * n_grid
        assert sched.visited == n_grid * (n_grid + 1) // 2
        return
    n = block // sched.sub
    assert sched.sub in (128, 256, 512) and n * sched.sub == block
    want = {(i, j): i == j for i in range(n) for j in range(i + 1)}
    assert len(sched.pairs) == len(want)          # each once
    assert {(i, j): m for i, j, m in sched.pairs} == want
    # The strips the kernels loop over hold exactly those pairs: q
    # sub-block i against columns 0 .. (i + 1) * sub.
    assert sched.strips() == [
        (slice(i * sched.sub, (i + 1) * sched.sub),
         slice(0, (i + 1) * sched.sub)) for i in range(n)]
    # Counted over the whole [t, t] square, in sub-blocks: the triangle.
    side = t // sched.sub
    assert sched.square == side * side
    assert sched.visited == side * (side + 1) // 2
    assert sched.visited / sched.square < 0.7


@pytest.mark.parametrize("t,block,causal,share", [
    (1024, 1024, True, 10 / 16), (2048, 1024, True, 36 / 64),
    (1024, 256, True, 10 / 16), (256, 256, True, 1.0),
    (1024, 1024, False, 1.0)],
    ids=["walked", "walked_two_blocks", "grid_skips_only", "one_small_block",
         "noncausal"])
def test_flash_schedule_annotation_reports_the_visited_share(
        monkeypatch, t, block, causal, share):
    """Tracing the op writes ``flash.schedule`` once a call site, with the
    schedule's own counts as its tags."""
    import importlib

    mod = importlib.import_module("ray_tpu.ops.flash_attention")
    seen = []

    def annotate(name, **tags):
        seen.append((name, tags))
        return contextlib.nullcontext()

    monkeypatch.setattr(mod.spans, "annotate", annotate)
    x = jax.ShapeDtypeStruct((1, t, 2, 64), jnp.float32)
    jax.eval_shape(functools.partial(flash_attention, causal=causal,
                                     block_q=block, block_k=block),
                   x, x, x)
    (name, tags), = seen
    assert name == "flash.schedule"
    assert tags["t"] == t and tags["block"] == block
    assert tags["visited"] / tags["square"] == pytest.approx(share)
    if causal:
        sched = mod.causal_schedule(t, block, block, 64)
        assert (tags["sub"], tags["visited"], tags["square"]) == \
            (sched.sub, sched.visited, sched.square)


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
    # fused custom_vjp folds each chunk's softmax-minus-onehot into the
    # grad einsums chunk-wise, so per-element rounding differs from the
    # autodiff whole-logits path (measured <=0.2% of the peak gradient
    # magnitude).
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
