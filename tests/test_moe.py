"""MoE layer (ops/moe.py: dropless top-k as sorted, grouped matmuls):
the op against a per-token loop, gradients, GPT-2 integration, and
expert-parallel execution on the virtual mesh (SURVEY §2.3 EP row —
VERDICT round-1 missing item 13)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.gpt2 import GPT2Config, gpt2_init, gpt2_loss_fn
import ray_tpu.ops.moe as moe_ops
from ray_tpu.ops.moe import (MoEMLP, compact_capacity, moe_counters,
                             moe_layers, moe_losses)


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


def _per_token_loop(p, x, k, gated, norm_topk, act, first=0, valid=None):
    """The layer's mathematics with no sort and no grouped matmul: each
    row, its k experts, one after another (float64 numpy).  Of a share
    (matrices of the experts from ``first`` on): the part of the experts
    held; rows not ``valid`` stay 0."""
    p = {n: np.asarray(v, np.float64) for n, v in p.items()}
    x = np.asarray(x, np.float64)
    logits = x @ p["router"]
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    y = np.zeros_like(x)
    held = p["w_down" if gated else "w_out"].shape[0]
    for s in range(x.shape[0]):
        if valid is not None and not valid[s]:
            continue
        chosen = np.argsort(-probs[s], kind="stable")[:k]
        w = probs[s, chosen] / (probs[s, chosen].sum() if norm_topk else 1)
        for wk, e in zip(w, chosen - first):
            if not 0 <= e < held:
                continue
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


# ------------------------------------ a share of the experts, compacted

def _share(first=0, gated=True, n=32, held=2, k=4, d=16, ff=24):
    return MoEMLP(d_model=d, d_ff=ff, num_experts=n, top_k=k, gated=gated,
                  norm_topk_prob=True, act=jax.nn.silu, dtype=jnp.float32,
                  first_expert=first, held_experts=held)


def _apply(layer, params, x, valid=None):
    y, state = jax.jit(lambda p, x: layer.apply(
        p, x, valid, mutable=["intermediates"]))(params, x)
    (stats,) = moe_layers(state["intermediates"])
    return y, stats


def _plain(monkeypatch):
    """The layer without its compact branch: the path every call took
    before there was one."""
    monkeypatch.setattr(moe_ops, "compact_capacity", lambda *a: None)


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "ungated"])
@pytest.mark.parametrize("mixed", [False, True], ids=["all_valid", "valid_mixed"])
@pytest.mark.parametrize("first", [0, 6, 30])
def test_compact_branch_equals_plain_path_and_loop(monkeypatch, first,
                                                   mixed, gated):
    """2 of 32 experts held, 512 rows x top-4: 2,048 pairs of which ~128
    have an expert here, in a capacity of 256 (twice the balanced share).
    The compact branch is taken and gives the per-token loop's result and
    the plain path's; 1e-5 as above (float32 sums in another order)."""
    layer = _share(first, gated)
    x = jax.random.normal(jax.random.PRNGKey(first), (1, 512, 16))
    params = jax.tree_util.tree_map(
        lambda w: 10.0 * w, layer.init(jax.random.PRNGKey(1), x))
    valid = None
    if mixed:
        valid = jnp.asarray(np.random.default_rng(first).random((1, 512))
                            < 0.7)
    assert compact_capacity(512 * 4, 2, 32) == 256
    y, stats = _apply(layer, params, x, valid)
    assert bool(stats["compact"]) and 0 < int(stats["load"].sum()) <= 256
    want = _per_token_loop(
        params["params"], x[0], 4, gated, True, _silu, first,
        None if valid is None else np.asarray(valid[0]))
    np.testing.assert_allclose(np.asarray(y[0]), want, atol=1e-5)
    assert float(np.abs(want).max()) > 1e-2
    _plain(monkeypatch)
    y_plain, stats_plain = _apply(layer, params, x, valid)
    assert not bool(stats_plain["compact"])
    np.testing.assert_array_equal(stats["load"], stats_plain["load"])
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_plain),
                               atol=1e-5)


@pytest.mark.parametrize("first", [0, 6])
def test_router_crowded_onto_the_share_takes_the_fallback(first):
    """Every row's first two choices are the two held experts: 1,024
    pairs have an expert here, four times the capacity of 256.  The call
    takes the plain path (``compact`` 0), no pair is dropped, and the
    result is the per-token loop's."""
    layer = _share(first)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(0), (1, 512, 16))) + 0.1
    params = jax.tree_util.tree_map(
        lambda w: 10.0 * w, layer.init(jax.random.PRNGKey(1), x))
    router = np.asarray(params["params"]["router"]).copy()
    router[:, first] = 1.0              # positive rows: the share wins
    router[:, first + 1] = 0.5
    params["params"]["router"] = jnp.asarray(router)
    y, stats = _apply(layer, params, x)
    assert stats["load"].tolist() == [512, 512] and not bool(stats["compact"])
    want = _per_token_loop(params["params"], x[0], 4, True, True, _silu,
                           first)
    np.testing.assert_allclose(np.asarray(y[0]), want, atol=1e-5)
    assert (np.abs(np.asarray(y[0])).sum(-1) > 0).all()


def test_the_shares_add_up_to_the_whole_layer_with_compaction_on():
    """tests/test_granite.py's property at a prefill shape: four shares
    of 2 of 8 experts over 128 rows x top-2 (256 pairs, capacity 128),
    each through the compact branch, add up to the layer that holds all
    eight, and every pair is computed once."""
    whole = _share(n=8, held=None, k=2)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 128, 16))
    params = jax.tree_util.tree_map(
        lambda w: 10.0 * w, whole.init(jax.random.PRNGKey(1), x))
    want, stats = _apply(whole, params, x)
    assert not bool(stats["compact"])
    total, pairs = 0.0, 0
    for rank in range(4):
        part = dict(params["params"])
        for name in ("w_gate", "w_up", "w_down"):
            part[name] = part[name][2 * rank:2 * rank + 2]
        y, stats = _apply(_share(2 * rank, n=8, held=2, k=2),
                          {"params": part}, x)
        assert bool(stats["compact"])
        total, pairs = total + y, pairs + int(stats["load"].sum())
    assert pairs == 128 * 2
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5)


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "ungated"])
def test_gradients_through_the_compact_branch_equal_the_plain_paths(
        monkeypatch, gated):
    """``lax.cond`` keeps the op differentiable: the gradient of every
    leaf and of the input through the compact branch is the plain
    path's."""
    layer = _share(6, gated)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 512, 16))
    params = jax.tree_util.tree_map(
        lambda w: 10.0 * w, layer.init(jax.random.PRNGKey(1), x))
    valid = jnp.asarray(np.random.default_rng(2).random((1, 512)) < 0.8)

    def grads():
        def loss(p, x):
            y, state = layer.apply(p, x, valid, mutable=["intermediates"])
            (stats,) = moe_layers(state["intermediates"])
            return jnp.sum(jnp.sin(y) * y), stats["compact"]
        return jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True))(
            params, x)

    got, took = grads()
    _plain(monkeypatch)
    want, took_plain = grads()
    assert bool(took) and not bool(took_plain)
    flat = jax.tree_util.tree_leaves_with_path(got)
    assert len(flat) == (5 if gated else 4)
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=1e-5 * scale,
                                   err_msg=jax.tree_util.keystr(path))


# ----------------------------------------- which programs hold the branch

def _conds(layer, rows, d=8):
    """The ``cond`` equations of the layer's jaxpr over ``rows`` rows."""
    x = jax.ShapeDtypeStruct((1, rows, d), jnp.float32)
    params = jax.eval_shape(lambda: layer.init(
        jax.random.PRNGKey(0), jnp.zeros(x.shape, x.dtype)))
    jaxpr = jax.make_jaxpr(lambda p, x: layer.apply(
        p, x, mutable=["intermediates"]))(params, x)
    return [eqn for eqn in jaxpr.jaxpr.eqns if eqn.primitive.name == "cond"]


# (experts, held, top-k): the three configurations that hold a share, the
# rows of their decode step and the first prefill bucket that engages.
SHARES = {"kimi-k2.5": (384, 12, 8, 16, 32),
          "granite-4.0-h-small": (72, 18, 10, 16, 128),
          "kimi-linear-48b-a3b": (256, 16, 8, 16, 32)}


@pytest.mark.parametrize("rows", [1, 16, 128, 4096])
@pytest.mark.parametrize("n,k", [(64, 8), (4, 2)], ids=["olmoe", "gpt2"])
def test_a_layer_that_holds_all_its_experts_has_no_branch(n, k, rows):
    layer = MoEMLP(d_model=8, d_ff=8, num_experts=n, top_k=k, gated=True)
    assert compact_capacity(rows * k, n, n) is None
    assert not _conds(layer, rows)


@pytest.mark.parametrize("name", SHARES)
def test_a_share_has_the_branch_at_its_prefill_buckets_only(name):
    """No ``cond`` in the decode step (``S x k`` = 128 | 160 | 128 round
    to one tile of 128 rows: more than half) nor in a bucket under the
    first that engages; one from there up, and its compact branch holds
    no array of ``S x k`` rows."""
    n, held, k, decode_rows, engages = SHARES[name]
    layer = MoEMLP(d_model=8, d_ff=24, num_experts=n, top_k=k, gated=True,
                   held_experts=held)
    assert not _conds(layer, decode_rows)
    bucket = 8
    while bucket < engages:
        assert not _conds(layer, bucket), bucket
        bucket *= 2
    for rows in (engages, 2 * engages, 4096):
        (eqn,) = _conds(layer, rows)
        capacity = compact_capacity(rows * k, held, n)
        assert capacity % 128 == 0 and capacity % 512 != 0
        assert rows * k > capacity >= 2 * rows * k * held / n
        plain, compact = eqn.params["branches"]   # index 0: predicate false

        def leading(branch):
            return {v.aval.shape[0] for e in branch.jaxpr.eqns
                    for v in e.outvars if len(v.aval.shape) == 2}
        assert rows * k in leading(plain)
        assert rows * k not in leading(compact)
        assert capacity in leading(compact)


@pytest.mark.parametrize("pairs,held,n,want", [
    (4096 * 8, 12, 384, 2048 + 128),    # Kimi-K2.5, a sixteenth of its pairs
    (1024 * 8, 12, 384, 512 + 128),
    (256 * 10, 18, 72, 1280),           # Granite: half, in tiles of 256
    (512 * 10, 18, 72, 2560 + 128),
    (2048 * 8, 16, 256, 2048 + 128),    # Kimi-Linear, an eighth
    (64 * 10, 18, 72, None),            # 320 round to 384: more than half
    (16 * 8, 12, 384, None)])           # a decode step
def test_capacity_is_twice_the_balanced_share_in_tiles_the_kernel_likes(
        pairs, held, n, want):
    """Whole tiles of 128 rows, and never a count that 512 divides: the
    compiler's grouped matmul would take 512-row tiles and multiply one
    for every small group."""
    assert compact_capacity(pairs, held, n) == want


def test_counters_hold_compact_as_their_fourth_entry():
    layer = _share()
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 512, 16))
    params = layer.init(jax.random.PRNGKey(1), x)
    _, state = layer.apply(params, x, mutable=["intermediates"])
    (row,) = np.asarray(moe_counters(state["intermediates"]))
    (stats,) = moe_layers(state["intermediates"])
    assert moe_ops.MOE_COUNTERS == ("pairs", "experts_hit", "max_load",
                                    "compact")
    assert row.tolist() == [int(stats["load"].sum()),
                            int((stats["load"] > 0).sum()),
                            int(stats["load"].max()), 1]


def test_engine_counts_the_prefills_routing_apart_from_the_decode_runs():
    """Tiny Kimi-K2 holding 2 of its 8 experts (top-2, two sparse
    layers): a prompt of 100 tokens prefills in bucket 128 (256 pairs a
    layer, capacity 128: compact), one of 5 in bucket 8 (no branch).
    ``stats()["moe_prefill"]`` counts each prefill's layers, pairs and
    compact layers; ``stats()["moe"]`` holds the decode runs alone."""
    import dataclasses

    from ray_tpu.llm.engine import EngineConfig, GenerationEngine
    from ray_tpu.models.kimi import KimiK2Config, kimi_k2_init

    cfg = dataclasses.replace(KimiK2Config.tiny(remat=False),
                              held_experts=2, first_expert=2)
    params = kimi_k2_init(cfg, jax.random.PRNGKey(7))
    engine = GenerationEngine(
        model_cfg=cfg, params=params, engine_cfg=EngineConfig(
            page_size=4, num_pages=64, max_batch=2))
    rng = np.random.default_rng(0)
    long = engine.submit(rng.integers(0, 256, 100).tolist(), max_tokens=1)
    while not long.finished:
        engine.step()
    stats = engine.stats()
    assert stats["step_errors"] == 0, stats["last_error"]
    first = stats["moe_prefill"]
    assert (first["layer_runs"], first["compact"]) == (2, 2)
    assert 0 < first["pairs"] < 2 * 100 * 2
    assert set(first) == {"layer_runs", "pairs", "compact"}
    assert "moe" not in stats           # no decode run yet
    short = engine.submit(rng.integers(0, 256, 5).tolist(), max_tokens=4)
    while not short.finished:
        engine.step()
    stats = engine.stats()
    assert stats["step_errors"] == 0, stats["last_error"]
    both = stats["moe_prefill"]
    assert (both["layer_runs"], both["compact"]) == (4, 2)
    decode = stats["moe"]
    assert set(decode) == {"layer_runs", "pairs", "experts_hit", "max_load"}
    assert decode["layer_runs"] == 2 * stats["attention"]["decode_runs"] > 0
    assert decode["pairs"] <= decode["layer_runs"] * 2
