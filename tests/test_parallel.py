"""Parallelism module on the 8-device virtual CPU mesh: mesh/sharding
rules, ring attention vs dense reference (values AND gradients), Ulysses,
pipeline parallelism vs sequential execution."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.parallel import (MeshSpec, create_mesh, pipeline_apply,
                              ring_attention, ulysses_attention)
from ray_tpu.parallel.sharding import ShardingRules, logical_sharding
from jax.sharding import PartitionSpec as P
from jax import shard_map


def dense_attention(q, k, v, causal=True):
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * d ** -0.5
    if causal:
        t = q.shape[1]
        mask = np.tril(np.ones((t, t), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(p.dtype)).astype(q.dtype)


def test_mesh_spec_resolve():
    spec = MeshSpec(data=-1, tensor=2).resolve(8)
    assert spec.data == 4 and spec.tensor == 2
    with pytest.raises(ValueError):
        MeshSpec(data=3).resolve(8)


def test_create_mesh_axes():
    mesh = create_mesh(MeshSpec(data=2, tensor=4))
    assert mesh.shape["data"] == 2 and mesh.shape["tensor"] == 4
    assert set(mesh.axis_names) == {"dcn", "data", "fsdp", "expert",
                                    "pipeline", "seq", "tensor"}


def test_sharding_rules_prune():
    mesh = create_mesh(MeshSpec(data=8))
    sh = logical_sharding(mesh, ("batch", "embed"))
    assert sh.spec == P(("data",), None)
    sh2 = logical_sharding(mesh, ("batch", "mlp"))  # tensor axis size 1
    assert sh2.spec == P(("data",), None)


@pytest.mark.parametrize("impl", ["flash", "lax"])
@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_dense(causal, impl):
    mesh = create_mesh(MeshSpec(seq=4, data=2))
    b, t, h, d = 2, 32, 4, 16
    key = jax.random.PRNGKey(0)
    q, k, v = jax.random.normal(key, (3, b, t, h, d), jnp.float32)

    spec = P(("data",), "seq", None, None)
    ring = shard_map(
        functools.partial(ring_attention, causal=causal, impl=impl),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    out = jax.jit(ring)(q, k, v)
    ref = dense_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("impl", ["flash", "lax"])
def test_ring_attention_gradients(impl):
    mesh = create_mesh(MeshSpec(seq=4, data=-1))
    b, t, h, d = 1, 16, 2, 8
    q, k, v = jax.random.normal(jax.random.PRNGKey(1), (3, b, t, h, d))

    spec = P(None, "seq", None, None)
    ring = shard_map(functools.partial(ring_attention, causal=True,
                                       impl=impl),
                     mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)

    def loss_ring(q, k, v):
        return jnp.sum(ring(q, k, v) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(dense_attention(q, k, v, True) ** 2)

    g1 = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=3e-5, rtol=3e-5)


def test_ulysses_matches_dense():
    mesh = create_mesh(MeshSpec(seq=4, data=-1))
    b, t, h, d = 2, 32, 8, 16  # heads divisible by seq axis
    q, k, v = jax.random.normal(jax.random.PRNGKey(2), (3, b, t, h, d))

    spec = P(None, "seq", None, None)
    uly = shard_map(functools.partial(ulysses_attention, causal=True),
                    mesh=mesh, in_specs=(spec, spec, spec),
                    out_specs=spec, check_vma=False)
    out = jax.jit(uly)(q, k, v)
    ref = dense_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_pipeline_matches_sequential():
    mesh = create_mesh(MeshSpec(pipeline=4, data=-1))
    s, b, dim = 4, 8, 16
    keys = jax.random.split(jax.random.PRNGKey(3), s)
    ws = jnp.stack([jax.random.normal(k, (dim, dim)) * 0.3 for k in keys])
    x = jax.random.normal(jax.random.PRNGKey(4), (b, dim))

    def stage_fn(w, h):
        return jnp.tanh(h @ w)

    piped = shard_map(
        functools.partial(pipeline_apply, stage_fn, num_microbatches=4),
        mesh=mesh, in_specs=(P("pipeline"), P(None)),
        out_specs=P(None), check_vma=False)
    out = jax.jit(lambda ws, x: piped(ws, x))(ws, x)

    ref = x
    for i in range(s):
        ref = stage_fn(ws[i], ref)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_pipeline_gradients_flow():
    mesh = create_mesh(MeshSpec(pipeline=4, data=-1))
    s, b, dim = 4, 8, 8
    ws = jax.random.normal(jax.random.PRNGKey(5), (s, dim, dim)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(6), (b, dim))

    def stage_fn(w, h):
        return jnp.tanh(h @ w)

    piped = shard_map(
        functools.partial(pipeline_apply, stage_fn, num_microbatches=2),
        mesh=mesh, in_specs=(P("pipeline"), P(None)),
        out_specs=P(None), check_vma=False)

    def loss(ws):
        return jnp.sum(piped(ws, x) ** 2)

    def ref_loss(ws):
        h = x
        for i in range(s):
            h = stage_fn(ws[i], h)
        return jnp.sum(h ** 2)

    g = jax.jit(jax.grad(loss))(ws)
    g_ref = jax.grad(ref_loss)(ws)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               atol=2e-5, rtol=2e-5)


# One sharded train step per MeshSpec layout over the 8-device CPU mesh
# (what the old driver entry point's multi-chip dry run guarded, without
# its subprocess): DP x SP x TP with ring attention, DP x EP x TP with
# MoE blocks, and a dcn axis stacked over data x tensor.
_MESH_LAYOUTS = {
    "dp_sp_tp": (MeshSpec(data=2, fsdp=1, seq=2, tensor=2),
                 dict(vocab_size=512, n_layer=2, n_head=4, d_model=128,
                      d_ff=256, max_seq=64, attn_impl="ring"), 4),
    "dp_ep_tp": (MeshSpec(data=2, expert=2, tensor=2),
                 dict(vocab_size=256, n_layer=2, n_head=4, d_model=64,
                      d_ff=128, max_seq=32, moe_num_experts=4,
                      moe_every=2), 4),
    "dcn_dp_tp": (MeshSpec(dcn=2, data=2, tensor=2),
                  dict(vocab_size=256, n_layer=2, n_head=4, d_model=64,
                       d_ff=128, max_seq=32), 8),
}


@pytest.mark.parametrize("layout", sorted(_MESH_LAYOUTS))
def test_sharded_train_step_on_meshspec_axes(layout):
    from ray_tpu.models.gpt2 import (GPT2Config, gpt2_init, gpt2_loss_fn,
                                     gpt2_param_axes)
    from ray_tpu.train.train_step import (TrainState, make_optimizer,
                                          make_sharded_train_step,
                                          shard_state)

    spec, model_kw, batch = _MESH_LAYOUTS[layout]
    mesh = create_mesh(spec, jax.devices()[:8])
    rules = ShardingRules()
    cfg = GPT2Config(mesh=mesh, rules=rules, remat=True, **model_kw)
    optimizer = make_optimizer(total_steps=10, warmup_steps=2)
    state = shard_state(
        TrainState.create(gpt2_init(cfg, jax.random.PRNGKey(0)),
                          optimizer),
        mesh, gpt2_param_axes, rules)
    step = make_sharded_train_step(
        lambda p, b: gpt2_loss_fn(cfg, p, b, loss_chunk=0), optimizer,
        mesh)
    tokens = jax.device_put(
        jnp.zeros((batch, cfg.max_seq + 1), jnp.int32),
        logical_sharding(mesh, ("batch", None), rules))
    state, metrics = step(state, {"tokens": tokens})
    assert np.isfinite(float(metrics["loss"])), metrics
    # The executable that ran is the ahead-of-time one, compiled for
    # this mesh.
    assert step.compiled() is not None
