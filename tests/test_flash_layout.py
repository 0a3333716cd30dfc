"""The flash kernels' layouts (PR 36, ``ops/flash_attention.py``'s module
docstring): ``packed`` (q, k, v read as ``[B, T, H*D]``, two heads of 64
or one of 128 to a 128-lane block) against ``folded`` (the transposes to
``[B*H, T, D]``) against the dense float32 reference, in interpret mode;
the fused-``qkv`` entry against the split one; the path taken is asserted
by the ``flash.schedule`` tags, not only by the numbers."""
import contextlib
import dataclasses
import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import flash_attention_qkv
from ray_tpu.ops.flash_attention import (flash_attention,
                                         flash_attention_with_lse)

flash = importlib.import_module("ray_tpu.ops.flash_attention")


def _dense(q, k, v, causal):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    if causal:
        t = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], s, -1e30)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return out, jax.nn.logsumexp(s, axis=-1).transpose(0, 2, 1)


def _qkv(t, h, d, b=1, seed=0):
    return [jax.random.normal(k, (b, t, h, d), jnp.float32)
            for k in jax.random.split(jax.random.PRNGKey(seed), 3)]


def _loss(f):
    """Reads ``out`` and ``lse``: the gradient carries both cotangents."""
    def fn(q, k, v):
        out, lse = f(q, k, v)
        return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))
    return fn


@pytest.fixture
def schedules(monkeypatch):
    """The tags of every ``flash.schedule`` written while tracing."""
    seen = []

    def annotate(name, **tags):
        seen.append((name, tags))
        return contextlib.nullcontext()

    monkeypatch.setattr(flash.spans, "annotate", annotate)
    return seen


def _close(a, b, tol):
    scale = float(jnp.max(jnp.abs(b))) + 1e-9
    assert float(jnp.max(jnp.abs(a - b))) / scale < tol


SHAPES = [(12, 64, 2), (2, 64, 2), (4, 128, 1), (3, 64, 0), (4, 32, 0)]


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("t", [256, 1024], ids=["t256_whole", "t1024_walked"])
@pytest.mark.parametrize("h,d,hpp", SHAPES,
                         ids=[f"h{h}_d{d}" for h, d, _ in SHAPES])
def test_packed_folded_and_dense_agree(monkeypatch, schedules, h, d, hpp, t,
                                       causal):
    """``out``, ``lse``, ``dq``, ``dk``, ``dv`` with the lse's cotangent
    in them: the layout the shape allows against the reference, and,
    where it packs, against the same shape folded."""
    q, k, v = _qkv(t, h, d)
    op = functools.partial(flash_attention_with_lse, causal=causal,
                           block_q=1024, block_k=1024)
    ref = functools.partial(_dense, causal=causal)
    assert flash._packs(h, d) == hpp
    got = op(q, k, v) + jax.grad(_loss(op), argnums=(0, 1, 2))(q, k, v)
    want = ref(q, k, v) + jax.grad(_loss(ref), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        _close(a, b, 2e-5)
    layout = "packed" if hpp else "folded"
    assert {(n, tags["layout"], tags["heads_per_program"])
            for n, tags in schedules} == {("flash.schedule", layout,
                                           max(hpp, 1))}
    if causal and t == 1024:
        assert all(tags["visited"] == 10 and tags["square"] == 16
                   for _, tags in schedules)
    if not hpp:
        return
    del schedules[:]
    monkeypatch.setattr(flash, "_packs", lambda h, d: 0)
    folded = op(q, k, v) + jax.grad(_loss(op), argnums=(0, 1, 2))(q, k, v)
    assert {tags["layout"] for _, tags in schedules} == {"folded"}
    for a, b in zip(got, folded):
        _close(a, b, 2e-5)
    if hpp == 1:
        # one head to a program either way: the same arithmetic
        np.testing.assert_array_equal(np.asarray(got[0]),
                                      np.asarray(folded[0]))


@pytest.mark.parametrize("h,d,t,layout", [
    (12, 64, 1024, "packed"), (2, 64, 256, "packed"),
    (4, 128, 512, "packed"), (3, 64, 256, "folded"),
    (4, 32, 512, "folded")],
    ids=["h12_d64_t1024", "h2_d64_t256", "h4_d128_t512",
         "h3_d64_falls_back", "h4_d32_falls_back"])
def test_fused_qkv_entry_matches_the_split_one(schedules, h, d, t, layout):
    """One ``[B, T, 3*H*D]`` array in, ``[B, T, H*D]`` out, and ONE
    cotangent back: the split op's three, side by side."""
    b = 2
    q, k, v = _qkv(t, h, d, b=b, seed=1)
    qkv = jnp.concatenate([x.reshape(b, t, h * d) for x in (q, k, v)], -1)
    w = jax.random.normal(jax.random.PRNGKey(2), (b, t, h * d))
    kw = dict(causal=True, block_q=1024, block_k=1024)

    def fused(qkv):
        return jnp.sum(flash_attention_qkv(qkv, h, **kw) * w)

    def split(q, k, v):
        return jnp.sum(flash_attention(q, k, v, **kw).reshape(b, t, -1) * w)

    out = flash_attention_qkv(qkv, h, **kw)
    assert out.shape == (b, t, h * d)
    assert {tags["layout"] for _, tags in schedules} == {layout}
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(flash_attention(q, k, v, **kw).reshape(b, t, -1)))
    got = jax.grad(fused)(qkv)
    want = jnp.concatenate(
        [g.reshape(b, t, h * d)
         for g in jax.grad(split, argnums=(0, 1, 2))(q, k, v)], -1)
    assert got.shape == qkv.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    ref = jax.grad(lambda q, k, v: jnp.sum(
        _dense(q, k, v, True)[0].reshape(b, t, -1) * w),
        argnums=(0, 1, 2))(q, k, v)
    _close(got, jnp.concatenate([g.reshape(b, t, h * d) for g in ref], -1),
           2e-5)


def test_the_packed_call_transposes_nothing(schedules):
    """No ``transpose`` in the traced program of a shape that packs: q,
    k, v, ``out`` and their cotangents stay ``[B, T, H*D]`` (merging H
    and D is a reshape); a shape that folds still has them."""
    def ops(h, d):
        x = jax.ShapeDtypeStruct((2, 256, h, d), jnp.float32)
        fn = jax.grad(lambda q, k, v: jnp.sum(flash_attention(q, k, v)),
                      argnums=(0, 1, 2))
        text = str(jax.make_jaxpr(fn)(x, x, x))
        return text.count("transpose[")

    assert ops(2, 64) == 0
    assert ops(3, 64) > 0


@pytest.mark.parametrize("h,per_shard", [(4, "packed"), (2, "folded")],
                         ids=["2_heads_a_shard_packed",
                              "1_head_a_shard_folds"])
def test_fused_path_under_shard_map_splits_the_heads(schedules, h,
                                                     per_shard):
    """``models/attention.py attention_qkv`` on a CPU mesh with the heads
    split two ways: each shard runs the same per-shard path on its own
    heads' q, k, v (its layout by its own shape) and the result is the
    unsharded one."""
    from jax.sharding import Mesh

    from ray_tpu.models.attention import attention_qkv, qkv_by_head
    from ray_tpu.models.gpt2 import GPT2Config
    from ray_tpu.parallel.sharding import logical_shards

    d, b, t = 64, 2, 256
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                ("fsdp", "tensor"))
    cfg = GPT2Config(n_head=h, d_model=h * d, attn_impl="flash",
                     dtype=jnp.float32)
    qkv = jax.random.normal(jax.random.PRNGKey(3), (b, t, 3 * h * d))
    w = jax.random.normal(jax.random.PRNGKey(4), (b, t, h * d))

    sharded = dataclasses.replace(cfg, mesh=mesh)

    def as_made(cfg, qkv):
        """``qkv`` as GPT-2's ``c_attn`` hands it over: as it stands on
        one device, by shard of the heads across a mesh ([shards, B, T,
        3 * H/shards * D]: ``models/gpt2.py FusedQKV``)."""
        if not qkv_by_head(cfg):
            return qkv
        n = logical_shards(cfg.mesh, "heads", h)
        assert n == 2
        return qkv.reshape(b, t, 3, n, -1).transpose(3, 0, 1, 2, 4) \
            .reshape(n, b, t, -1)

    def loss(cfg):
        return lambda qkv: jnp.sum(
            attention_qkv(cfg, as_made(cfg, qkv), h) * w)

    want, dwant = jax.value_and_grad(loss(cfg))(qkv)
    del schedules[:]
    got, dgot = jax.jit(jax.value_and_grad(loss(sharded)))(qkv)
    assert {(tags["layout"], tags["heads_per_program"])
            for _, tags in schedules} == {
        (per_shard, 2 if per_shard == "packed" else 1)}
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    _close(dgot, dwant, 2e-5)
    q, k, v = (x.reshape(b, t, h, d) for x in jnp.split(qkv, 3, axis=-1))
    _close(attention_qkv(sharded, as_made(sharded, qkv), h),
           _dense(q, k, v, True)[0].reshape(b, t, -1), 2e-5)
