"""MoE layer (ops/moe.py: dropless top-k as sorted, grouped matmuls):
the op against a per-token loop, gradients, GPT-2 integration, and
expert-parallel execution on the virtual mesh (SURVEY §2.3 EP row —
VERDICT round-1 missing item 13)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.gpt2 import GPT2Config, gpt2_init, gpt2_loss_fn
from ray_tpu.ops.moe import MoEMLP, moe_layers, moe_losses


def _layer(e=4, k=2, d=16, ff=32):
    return MoEMLP(d_model=d, d_ff=ff, num_experts=e, top_k=k,
                  dtype=jnp.float32)


def test_moe_forward_shape_and_grads():
    layer = _layer()
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16))
    params = layer.init(jax.random.PRNGKey(1), x)

    def loss(p):
        y, state = layer.apply(p, x, mutable=["intermediates"])
        aux = moe_losses(state["intermediates"])
        return jnp.mean(y ** 2) + 0.01 * aux["load_balancing"] \
            + 0.001 * aux["router_z"]

    val, grads = jax.value_and_grad(loss)(params)
    assert np.isfinite(float(val))
    flat = jax.tree_util.tree_leaves(grads)
    assert all(np.all(np.isfinite(g)) for g in flat)
    # Router AND experts both receive gradient.
    g = grads["params"]
    assert float(jnp.abs(g["router"]).sum()) > 0
    assert float(jnp.abs(g["w_in"]).sum()) > 0


def _per_token_loop(p, x, k, gated, norm_topk, act):
    """The layer's mathematics with no sort and no grouped matmul: each
    row, its k experts, one after another (float64 numpy)."""
    p = {n: np.asarray(v, np.float64) for n, v in p.items()}
    x = np.asarray(x, np.float64)
    logits = x @ p["router"]
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    y = np.zeros_like(x)
    for s in range(x.shape[0]):
        chosen = np.argsort(-probs[s], kind="stable")[:k]
        w = probs[s, chosen] / (probs[s, chosen].sum() if norm_topk else 1)
        for wk, e in zip(w, chosen):
            if gated:
                h = act(x[s] @ p["w_gate"][e]) * (x[s] @ p["w_up"][e])
                y[s] += wk * (h @ p["w_down"][e])
            else:
                y[s] += wk * (act(x[s] @ p["w_in"][e]) @ p["w_out"][e])
    return y


def _silu(z):
    return z / (1.0 + np.exp(-z))


@pytest.mark.parametrize("s,e,k", [
    (s, e, k) for s in (1, 13, 128) for e in (8, 64) for k in (2, 8)])
def test_moe_equals_per_token_loop(s, e, k):
    """Gated experts with the softmax's own weights (OLMoE's case) at
    S x E x k from one row to more rows than experts, more pairs than
    experts and fewer.  1e-5: float32 sums in another order (the sort);
    a dropped or doubled pair would show at ~1e-2."""
    layer = MoEMLP(d_model=16, d_ff=24, num_experts=e, top_k=k, gated=True,
                   norm_topk_prob=False, act=jax.nn.silu,
                   dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(s + e + k), (1, s, 16))
    params = layer.init(jax.random.PRNGKey(1), x)
    # std 0.02 weights give outputs of ~1e-4; scale them up to O(0.1)
    params = jax.tree_util.tree_map(lambda w: 10.0 * w, params)
    y, state = jax.jit(lambda p, x: layer.apply(
        p, x, mutable=["intermediates"]))(params, x)
    want = _per_token_loop(params["params"], x[0], k, True, False, _silu)
    np.testing.assert_allclose(np.asarray(y[0]), want, atol=1e-5)
    (stats,) = moe_layers(state["intermediates"])
    assert int(stats["load"].sum()) == s * k      # every pair, once


def test_moe_ungated_renormalised_case_equals_loop():
    """GPT-2's option: two-matrix GELU experts, weights renormalised."""
    layer = _layer(e=4, k=2)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16))
    params = jax.tree_util.tree_map(
        lambda w: 10.0 * w, layer.init(jax.random.PRNGKey(1), x))
    y = layer.apply(params, x)
    gelu = lambda z: np.asarray(jax.nn.gelu(jnp.asarray(z)))  # noqa: E731
    want = _per_token_loop(params["params"], x.reshape(16, 16), 2, False,
                           True, gelu)
    np.testing.assert_allclose(np.asarray(y).reshape(16, 16), want,
                               atol=1e-5)


def test_routed_scaling_factor_multiplies_the_renormalised_weights():
    """``routed_scaling_factor`` (Kimi-K2: 2.827), ``MoEMLP``'s field: the
    same experts, the weights after their renormalisation times the
    factor, so the layer's output times the factor; 1, the default, is no
    operation at all (the other families' programs keep their text)."""
    x = jnp.asarray(np.random.default_rng(4).normal(size=(2, 9, 16)),
                    jnp.float32)
    kw = dict(d_model=16, d_ff=32, num_experts=8, top_k=2,
              scoring="sigmoid", select_bias=True, norm_eps=1e-20,
              dtype=jnp.float32)
    plain, scaled = MoEMLP(**kw), MoEMLP(routed_scaling_factor=2.827, **kw)
    params = plain.init(jax.random.PRNGKey(0), x)
    y, sown = plain.apply(params, x, mutable=["intermediates"])
    y2, sown2 = scaled.apply(params, x, mutable=["intermediates"])
    assert float(jnp.std(y)) > 0
    np.testing.assert_allclose(y2, 2.827 * y, rtol=1e-5, atol=1e-7)
    np.testing.assert_array_equal(sown["intermediates"]["moe"][0]["load"],
                                  sown2["intermediates"]["moe"][0]["load"])
    one = MoEMLP(routed_scaling_factor=1.0, **kw)
    assert jax.jit(lambda p: one.apply(p, x)).lower(params).as_text() == \
        jax.jit(lambda p: plain.apply(p, x)).lower(params).as_text()
    assert "2.827" in jax.jit(lambda p: scaled.apply(p, x)).lower(
        params).as_text()


def test_moe_collapsed_router_still_serves_every_token():
    """Dropless: with the router collapsed onto one expert (every row's
    first choice, 64 rows in one group) no row is dropped, and the result
    is still the per-token loop's."""
    layer = MoEMLP(d_model=16, d_ff=24, num_experts=8, top_k=2, gated=True,
                   norm_topk_prob=False, act=jax.nn.silu,
                   dtype=jnp.float32)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(0), (1, 64, 16))) + 0.1
    params = jax.tree_util.tree_map(
        lambda w: 10.0 * w, layer.init(jax.random.PRNGKey(1), x))
    router = np.zeros((16, 8), np.float32)
    router[:, 3] = 1.0                  # positive rows: expert 3 wins
    router[:, 5] = 0.5
    params["params"]["router"] = jnp.asarray(router)
    y, state = layer.apply(params, x, mutable=["intermediates"])
    (stats,) = moe_layers(state["intermediates"])
    assert stats["load"].tolist() == [0, 0, 0, 64, 0, 64, 0, 0]
    want = _per_token_loop(params["params"], x[0], 2, True, False, _silu)
    np.testing.assert_allclose(np.asarray(y[0]), want, atol=1e-5)
    assert (np.abs(np.asarray(y[0])).sum(-1) > 0).all()


def test_moe_invalid_rows_take_no_part():
    """Rows marked invalid go to no expert, count nowhere, and leave the
    valid rows' results as they are without them."""
    layer = MoEMLP(d_model=16, d_ff=24, num_experts=8, top_k=2, gated=True,
                   act=jax.nn.silu, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 1, 16))
    params = layer.init(jax.random.PRNGKey(1), x)
    valid = jnp.asarray([True, False, True, True, False, False, False,
                         False])[:, None]
    y, state = layer.apply(params, x, valid, mutable=["intermediates"])
    alone = layer.apply(params, x[np.asarray(valid[:, 0])])
    np.testing.assert_array_equal(np.asarray(y)[np.asarray(valid[:, 0])],
                                  np.asarray(alone))
    assert not np.asarray(y)[~np.asarray(valid[:, 0])].any()
    (stats,) = moe_layers(state["intermediates"])
    assert int(stats["load"].sum()) == 3 * 2


def test_moe_aux_loss_balanced_vs_skewed():
    """The Switch aux loss is minimal (=1) for a uniform router and
    larger for a collapsed one."""
    e = 4
    s = 1024
    probs_uniform = jnp.full((s, e), 1 / e)
    probs_skewed = jnp.concatenate(
        [jnp.full((s, 1), 0.97), jnp.full((s, e - 1), 0.01)], axis=1)
    for probs, expect_min in ((probs_uniform, True),
                              (probs_skewed, False)):
        idx = jnp.argmax(probs, -1)
        f = jax.nn.one_hot(idx, e).mean(0)
        p = probs.mean(0)
        aux = float(e * jnp.sum(f * p))
        if expect_min:
            assert abs(aux - 1.0) < 1e-5
        else:
            assert aux > 2.0


def test_gpt2_moe_trains():
    from ray_tpu.train.train_step import (TrainState, make_optimizer,
                                          make_train_step)

    cfg = GPT2Config(vocab_size=256, n_layer=2, n_head=2, d_model=64,
                     d_ff=128, max_seq=32, remat=False,
                     dtype=jnp.float32, moe_num_experts=4, moe_every=2)
    params = gpt2_init(cfg, jax.random.PRNGKey(0))
    # MoE params exist on the alternating layer only.
    assert "moe_mlp" in params["params"]["h_1"]
    assert "moe_mlp" not in params["params"]["h_0"]
    opt = make_optimizer(total_steps=30)
    state = TrainState.create(params, opt)
    step = make_train_step(
        lambda p, b: gpt2_loss_fn(cfg, p, b, loss_chunk=0), opt)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0, 256)
    losses = []
    for _ in range(12):
        state, m = step(state, {"tokens": tokens})
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses


def test_gpt2_moe_expert_parallel_mesh():
    """Full sharded train step with a real expert mesh axis on the
    8-device virtual CPU mesh (DP x EP x TP), the state placed by the
    family's partition rules."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel import gang_mesh
    from ray_tpu.parallel.partition_rules import tree_shardings
    from ray_tpu.train import distributed as dist
    from ray_tpu.train.train_step import (TrainState, make_optimizer,
                                          make_sharded_train_step)

    mesh = gang_mesh({"data": 2, "expert": 2, "tensor": 2})
    cfg = GPT2Config(vocab_size=128, n_layer=2, n_head=4, d_model=64,
                     d_ff=128, max_seq=32, remat=True, mesh=mesh,
                     moe_num_experts=4, moe_every=2)
    opt = make_optimizer(total_steps=10)
    state, specs = dist.shard_train_state(
        TrainState.create(gpt2_init(cfg, jax.random.PRNGKey(0)), opt),
        mesh, dist.rules_for_model("gpt2"))
    # Expert weights are actually sharded over the expert axis.
    w_in = state.params["params"]["h_1"]["moe_mlp"]["w_in"]
    assert w_in.sharding.spec == P("expert", None, "tensor")
    assert not w_in.sharding.is_fully_replicated
    step = make_sharded_train_step(
        lambda p, b: gpt2_loss_fn(cfg, p, b, loss_chunk=0), opt,
        mesh=mesh, state_shardings=tree_shardings(mesh, specs),
        batch_sharding=dist.batch_sharding(mesh))
    tokens = jax.device_put(jnp.zeros((4, 33), jnp.int32),
                            dist.batch_sharding(mesh))
    state, metrics = step(state, {"tokens": tokens})
    jax.block_until_ready(metrics)
    assert np.isfinite(float(metrics["loss"]))
