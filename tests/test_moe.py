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
from ray_tpu.ops.moe import (MoEMLP, combine_blocks, compact_capacity,
                             moe_counters, moe_layers, moe_losses)


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


@pytest.mark.parametrize("n,rows,blocks", [(8, 128, 1), (16, 4096, 32)],
                         ids=["one_block", "blocks"])
def test_the_shares_add_up_to_the_whole_layer_with_compaction_on(
        n, rows, blocks):
    """tests/test_granite.py's property at a prefill shape: the shares
    of 2 of ``n`` experts each, every one through the compact branch,
    add up to the layer that holds them all, and every pair is computed
    once.  Four shares over 128 rows x top-2 (256 pairs, capacity 128:
    one placement matmul), and eight over 4,096 rows (capacity 2,176:
    combined by 32 blocks of tokens)."""
    assert combine_blocks(rows, 2, compact_capacity(rows * 2, 2, n)) \
        == blocks
    whole = _share(n=n, held=None, k=2)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, rows, 16))
    params = jax.tree_util.tree_map(
        lambda w: 10.0 * w, whole.init(jax.random.PRNGKey(1), x))
    want, stats = _apply(whole, params, x)
    assert not bool(stats["compact"])
    total, pairs = 0.0, 0
    for first in range(0, n, 2):
        part = dict(params["params"])
        for name in ("w_gate", "w_up", "w_down"):
            part[name] = part[name][first:first + 2]
        y, stats = _apply(_share(first, n=n, held=2, k=2),
                          {"params": part}, x)
        assert bool(stats["compact"])
        total, pairs = total + y, pairs + int(stats["load"].sum())
    assert pairs == rows * 2
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5)


def _assert_compact_grads_equal_plain(monkeypatch, layer, params, x,
                                      valid=None):
    """Every leaf's gradient and the input's through the compact branch
    equal the plain path's; returns the leaves."""
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
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=1e-5 * scale,
                                   err_msg=jax.tree_util.keystr(path))
    return flat


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
    flat = _assert_compact_grads_equal_plain(monkeypatch, layer, params, x,
                                             valid)
    assert len(flat) == (5 if gated else 4)


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


# ------------------------------------------- the combine by blocks of tokens

# 4,096 rows x top-2 on 2 of 16 experts: 8,192 pairs in a capacity of
# 2,176, which ``combine_blocks`` sends through 32 blocks of 128 tokens,
# each over a window of 256 rows.
BLOCKED = dict(n=16, held=2, k=2)
BLOCKED_ROWS, BLOCKED_CAPACITY, BLOCKED_BLOCKS = 4096, 2176, 32


def _blocked_case(routing, first, seed=0):
    """(layer, params, x) whose router sends the rows as ``routing``
    says: ``random``; ``one_token`` (row 1,005 holds both its pairs here,
    its ten neighbours none); ``full_tail`` (the last 1,000 rows hold
    both their pairs here and no other row any: 2,000 rows of the 2,176,
    every window of the tail full to its bound of 128 x k rows, and the
    last ones moved back from the end)."""
    layer = _share(first, **BLOCKED)
    x = np.array(jax.random.normal(jax.random.PRNGKey(seed),
                                   (1, BLOCKED_ROWS, 16)))
    params = jax.tree_util.tree_map(
        lambda w: 10.0 * w, layer.init(jax.random.PRNGKey(1),
                                       jnp.asarray(x)))
    x[0, :, 0] = 0.0
    if routing == "one_token":
        x[0, 1000:1011, 0] = -5.0
        x[0, 1005, 0] = 5.0
    elif routing == "full_tail":
        x[0, :, 0] = -5.0
        x[0, -1000:, 0] = 5.0
    router = np.asarray(params["params"]["router"]).copy()
    router[0] = 0.0
    router[0, first:first + 2] = 3.0    # feature 0 decides for the share
    params["params"]["router"] = jnp.asarray(router)
    return layer, params, jnp.asarray(x)


@pytest.mark.parametrize("mixed", [False, True],
                         ids=["all_valid", "valid_mixed"])
@pytest.mark.parametrize("routing,first", [
    ("random", 0), ("random", 14), ("one_token", 6), ("full_tail", 6)])
def test_blocked_combine_equals_plain_path_and_loop(monkeypatch, routing,
                                                    first, mixed):
    """The compact branch at a shape the rule sends through blocks gives
    the per-token loop's result and the plain path's, whatever the
    routing: a token with all its k pairs here between neighbours with
    none, and windows full to the bound at the end of the rows."""
    assert compact_capacity(BLOCKED_ROWS * 2, 2, 16) == BLOCKED_CAPACITY
    assert combine_blocks(BLOCKED_ROWS, 2, BLOCKED_CAPACITY) \
        == BLOCKED_BLOCKS
    layer, params, x = _blocked_case(routing, first)
    valid = None
    if mixed:
        valid = np.random.default_rng(first).random((1, BLOCKED_ROWS)) < 0.9
        valid[0, 1005] = True
        valid = jnp.asarray(valid)
    y, stats = _apply(layer, params, x, valid)
    pairs = int(stats["load"].sum())
    assert bool(stats["compact"]) and 0 < pairs <= BLOCKED_CAPACITY
    if routing == "full_tail" and not mixed:
        assert pairs == 2000 > BLOCKED_CAPACITY - 128 * 2
    want = _per_token_loop(
        params["params"], x[0], 2, True, True, _silu, first,
        None if valid is None else np.asarray(valid[0]))
    if routing == "one_token":
        held = np.abs(want[1000:1011]).sum(-1) > 0
        assert held.tolist() == [False] * 5 + [True] + [False] * 5
    np.testing.assert_allclose(np.asarray(y[0]), want, atol=1e-5)
    assert float(np.abs(want).max()) > 1e-2
    _plain(monkeypatch)
    y_plain, stats_plain = _apply(layer, params, x, valid)
    assert not bool(stats_plain["compact"])
    np.testing.assert_array_equal(stats["load"], stats_plain["load"])
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_plain),
                               atol=1e-5)


@pytest.mark.parametrize("routing", ["random", "full_tail"])
def test_gradients_through_the_blocked_combine_equal_the_plain_paths(
        monkeypatch, routing):
    """The scan over blocks is differentiable: every leaf's gradient and
    the input's are the plain path's."""
    layer, params, x = _blocked_case(routing, 6, seed=2)
    _assert_compact_grads_equal_plain(monkeypatch, layer, params, x)


def _walk(jaxpr, turns=1):
    """(equation, how often it runs) of a jaxpr and of every jaxpr under
    it; a ``scan``'s body runs ``length`` times."""
    for eqn in jaxpr.eqns:
        yield eqn, turns
        inner = turns * eqn.params.get("length", 1) \
            if eqn.primitive.name == "scan" else turns
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub, inner)


def _compact_branch(layer, rows, d):
    (eqn,) = _conds(layer, rows, d)
    return eqn.params["branches"][1].jaxpr


def _dot_flops(jaxpr):
    total = 0
    for eqn, turns in _walk(jaxpr):
        if eqn.primitive.name == "dot_general":
            (contract, _), _ = eqn.params["dimension_numbers"]
            inner = np.prod([eqn.invars[0].aval.shape[i] for i in contract])
            total += 2 * turns * int(inner) * int(
                np.prod(eqn.outvars[0].aval.shape))
    return total


def test_the_blocked_branch_holds_no_array_of_tokens_by_capacity():
    """Command A+'s 4,096 bucket (16 of 128, top-8: 8,320 rows): in the
    compact branch no array has ``S`` and ``capacity`` as its two
    dimensions nor ``S x k`` rows, and its placement matmuls multiply
    ``S x (128 x k) x d``, where one block multiplies ``S x capacity x
    d``."""
    rows, d, k = 4096, 8, 8
    layer = MoEMLP(d_model=d, d_ff=24, num_experts=128, top_k=k,
                   gated=True, held_experts=16)
    capacity = compact_capacity(rows * k, 16, 128)
    blocks = combine_blocks(rows, k, capacity)
    assert (capacity, blocks) == (8320, 32)
    branch = _compact_branch(layer, rows, d)
    shapes = {tuple(v.aval.shape) for eqn, _ in _walk(branch)
              for v in eqn.outvars}
    assert not [s for s in shapes if rows in s and capacity in s]
    assert not [s for s in shapes if len(s) > 1 and rows * k in s]
    assert (capacity, d) in shapes
    assert _dot_flops(branch) == rows * (rows // blocks * k) * d * 2
    # one block, at the bucket below the rule's edge: the whole square
    small = _compact_branch(layer, 1024, d)
    assert combine_blocks(1024, k, 2176) == 1
    assert _dot_flops(small) == 1024 * 2176 * d * 2


# (rows, experts, held, top-k) -> sha256[:16] of ``lower().as_text()`` of
# the layer's forward and of its gradient (d 8, d_ff 24, gated, bf16),
# read from PR 53's tree, the parent of the PR that taught the combine
# its blocks: shapes where the rule keeps ONE block.
ONE_BLOCK_PARENT_TEXT = {
    (4096, 384, 12, 8): ("05bafec921e63b42", "2438b8914f3438f8"),   # Kimi
    (1024, 384, 12, 8): ("83f0aff9857a59e4", "c26652d991a8bcac"),
    (2048, 256, 16, 8): ("8604edff3c18cab5", "047c31e942cbdb5d"),   # -Linear
    (1024, 72, 18, 10): ("ca2a707a04cafebb", "71978db29a38f5c5"),   # Granite
    (1024, 128, 16, 8): ("0a45488f99ab7b14", "a33a580defcac2f3"),   # Command A+
}


@pytest.mark.parametrize("shape", sorted(ONE_BLOCK_PARENT_TEXT))
def test_one_block_lowers_to_the_parents_text(shape):
    """The largest prefill bucket of each cell that keeps one block (and
    Command A+'s smallest): forward and gradient lower to the text they
    lowered to before the combine knew blocks, letter for letter."""
    rows, n, held, k = shape
    assert combine_blocks(rows, k, compact_capacity(rows * k, held, n)) == 1
    assert _lowered_texts(rows, n, held, k) == ONE_BLOCK_PARENT_TEXT[shape]


def _lowered_texts(rows, n, held, k):
    import hashlib

    layer = MoEMLP(d_model=8, d_ff=24, num_experts=n, top_k=k, gated=True,
                   held_experts=held)
    x = jax.ShapeDtypeStruct((1, rows, 8), jnp.float32)
    params = jax.eval_shape(lambda: layer.init(
        jax.random.PRNGKey(0), jnp.zeros(x.shape, x.dtype)))

    def forward(p, x):
        return layer.apply(p, x, mutable=["intermediates"])

    def loss(p, x):
        return jnp.sum(forward(p, x)[0].astype(jnp.float32) ** 2)
    return tuple(
        hashlib.sha256(jax.jit(f).lower(params, x).as_text().encode()
                       ).hexdigest()[:16]
        for f in (forward, jax.grad(loss, argnums=(0, 1))))


# configuration -> (experts, held, top-k, {prefill bucket: blocks}): every
# bucket of the four cells that run the compact branch, and Kimi-Linear's
# 4,096 that none runs.  1: the one placement matmul.
COMBINE_BLOCKS = {
    "command-a-plus-05-2026": (128, 16, 8, {1024: 1, 2048: 16, 4096: 32,
                                            8192: 64, 16384: 128}),
    "kimi-k2.5": (384, 12, 8, {1024: 1, 4096: 1}),
    "kimi-linear-48b-a3b": (256, 16, 8, {2048: 1, 4096: 32}),
    "granite-4.0-h-small": (72, 18, 10, {512: 1, 1024: 1}),
}


@pytest.mark.parametrize("name,bucket", [
    (name, bucket) for name, row in COMBINE_BLOCKS.items()
    for bucket in row[3]])
def test_the_rule_by_configuration_and_bucket(name, bucket):
    """Blocks of 128 tokens where one block would multiply at least four
    times as much and the square is large enough to pay for the second
    sort; the one matmul elsewhere."""
    n, held, k, want = COMBINE_BLOCKS[name]
    capacity = compact_capacity(bucket * k, held, n)
    blocks = combine_blocks(bucket, k, capacity)
    assert blocks == want[bucket]
    if blocks > 1:
        assert bucket % blocks == 0 and bucket // blocks * k <= capacity


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
