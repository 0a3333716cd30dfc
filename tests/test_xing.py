"""Xing4.0 (models/xing.py: Kimi-K2's latent attention and routed experts
joined by FOUR residual streams that every sublayer reads, writes and
mixes through maps it computes from them: manifold-constrained
hyper-connections) held to its plain float32 reference
(benchmark/reference/xing4_0_ref.py) at a tiny size on the CPU: two dense
and two sparse layers, 64 wide over 4 streams, 4 heads over ranks 32 | 24
and widths 16 | 8 | 16, top-2 of 8 experts of width 32, 20 rounds of the
Sinkhorn.  Through the model, the engine's jitted forward with the latent
pool, the Sinkhorn by hand, one stream for four, the expert shares, the
loss, the scopes, the counters and the family registry."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import xing4_0_ref as ref
from benchmark.tools import kimi_faults, xing_faults
from ray_tpu.models import MODEL_FAMILIES, CacheSpec, family_of
from ray_tpu.models.kimi import KimiK2, KimiK2Config
from ray_tpu.models.xing import (Xing, XingConfig, sinkhorn, xing_init,
                                 xing_loss_fn)

CFG = XingConfig.tiny(remat=False)
# The reference's configuration: the source's keys at the tiny size.
CONFIG = {"num_hidden_layers": 4, "hidden_size": 64,
          "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 24,
          "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
          "first_k_dense_replace": 2, "n_routed_experts": 8,
          "n_shared_experts": 1, "num_experts_per_tok": 2,
          "norm_topk_prob": True, "scoring_func": "sigmoid",
          "n_group": 1, "topk_group": 1, "routed_scaling_factor": 2.0,
          "num_nextn_predict_layers": 0, "hc_mult": 4,
          "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
          "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
          "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
          "rope_scaling": {"type": "yarn", "factor": 4.0,
                           "original_max_position_embeddings": 32,
                           "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                           "mscale_all_dim": 1}}


def _scaled(params, factor=8.0):
    """std-0.02 matrices at 64 wide leave every router, softmax and map
    near its bias; scaled up, routing, attention and the maps' input-
    dependent parts are decided and an error of the mathematics shows
    (tests/test_kimi.py).  The 1-D leaves (norm scales, expert_bias, the
    maps' biases and gates) stay as drawn."""
    return jax.tree_util.tree_map(
        lambda w: w if w.ndim == 1 else factor * w, params)


@pytest.fixture(scope="module")
def params():
    return _scaled(xing_init(CFG, jax.random.PRNGKey(7)))


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 45)),
                       jnp.int32)


# ------------------------------------------------ forward against reference

def test_forward_equals_reference(params, tokens):
    """The full forward against the reference over 45 positions; logits
    of size ~1; and the maps are live: with their input-dependent part
    left out the logits move."""
    want = ref.forward(CONFIG, params, tokens)
    assert float(jnp.std(want)) > 0.05
    got = jax.jit(lambda p, t: Xing(CFG).apply(p, t))(params, tokens)
    np.testing.assert_allclose(got, want, atol=5e-5)
    with xing_faults.fault("maps_input_independent", CFG, params) as (_, p):
        moved = ref.forward(CONFIG, p, tokens)
    assert float(jnp.max(jnp.abs(moved - want))) > 1e-2


def test_the_reference_in_blocks_equals_the_reference_whole(params, tokens,
                                                            monkeypatch):
    """``forward`` attends in blocks of query positions so that 4,096
    fit, and ``by_layer`` runs a layer a jit with the experts masked:
    blocks of 16 over 45 positions (a ragged last one) give what one block
    gives, by layer what eager gives, ``last`` the last positions."""
    whole = ref.forward(CONFIG, params, tokens)
    monkeypatch.setattr(ref, "ATTN_BLOCK", 16)
    np.testing.assert_allclose(ref.forward(CONFIG, params, tokens), whole,
                               atol=5e-5)
    np.testing.assert_allclose(
        ref.forward(CONFIG, params, tokens, last=7, by_layer=True),
        whole[:, -7:], atol=5e-5)
    # rows filled behind their own lengths: what lies behind changes nothing
    np.testing.assert_allclose(
        ref.forward(CONFIG, params, tokens, last=3, lengths=[45, 30],
                    by_layer=True)[1], whole[1, 27:30], atol=5e-5)


# ------------------------------------------------------- the maps, by hand

def test_sinkhorn_equals_a_4_x_4_worked_out_by_hand():
    """``M_0 = exp(S)`` with S = ln of [[1, 2, 1, 1], [2, 1, 1, 1],
    [1, 1, 3, 1], [1, 1, 1, 1]].  Round 1: the column sums are 5, 5, 6,
    4; dividing, row 0 is (1/5, 2/5, 1/6, 1/4), whose sum is 61/60, so
    H[0, 1] after one round is (2/5) / (61/60) = 24/61, and row 3 (1/5,
    1/5, 1/6, 1/4) sums to 49/60, so H[3, 3] = (1/4) / (49/60) = 15/49.
    After 20 rounds rows and columns sum to 1 within 1e-5 and the matrix
    is symmetric as ``M_0`` is; with ``hc_eps`` every sum is a sum + eps.
    The reference's loop over a [4, 4] array gives the same."""
    m0 = np.array([[1, 2, 1, 1], [2, 1, 1, 1], [1, 1, 3, 1], [1, 1, 1, 1]],
                  np.float32)
    one = np.array(sinkhorn(jnp.asarray(m0), 1, 0.0))
    np.testing.assert_allclose(one[0, 1], 24 / 61, rtol=1e-6)
    np.testing.assert_allclose(one[3, 3], 15 / 49, rtol=1e-6)
    np.testing.assert_allclose(one.sum(axis=1), 1.0, atol=1e-6)
    assert abs(one.sum(axis=0) - 1.0).max() > 0.01   # columns not yet
    full = np.array(sinkhorn(jnp.asarray(m0), 20, 1e-6))
    np.testing.assert_allclose(full.sum(axis=1), 1.0, atol=1e-5)
    np.testing.assert_allclose(full.sum(axis=0), 1.0, atol=1e-5)
    np.testing.assert_allclose(full, full.T, atol=1e-5)
    np.testing.assert_allclose(ref.sinkhorn(jnp.asarray(m0), 20, 1e-6),
                               full, atol=1e-6)
    # eps enters every sum: one round of a 1 x 1 is m / (m + eps), twice
    tiny = sinkhorn(jnp.full((1, 1), 1e-6, jnp.float32), 1, 1e-6)
    np.testing.assert_allclose(tiny, 0.5 / (0.5 + 1e-6), rtol=1e-6)
    # over tokens: [n, n, tokens], each token's matrix on its own
    both = sinkhorn(jnp.stack([jnp.asarray(m0), jnp.asarray(m0.T * 2)],
                              axis=-1), 20, 1e-6)
    np.testing.assert_allclose(both[..., 0], full, atol=1e-6)


def test_h_res_is_doubly_stochastic_and_neither_identity_nor_uniform(
        params, tokens):
    """The maps of layer 2's FFN sublayer on a real state: ``H_res``'s rows
    and columns sum to 1 within 1e-5, its entries differ from token to
    token, its diagonal is neither 1 nor 1/4; ``H_pre`` in (0, 1),
    ``H_post`` in (0, 2); and the model sows the same row and column
    errors the reference's maps show."""
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 9, 4, 64)),
                    jnp.float32)
    hc = params["params"]["layer_2"]["mlp_hc"]
    h_pre, h_post, h_res = ref.maps(x, hc, CONFIG)
    rows, cols = h_res.sum(axis=-1), h_res.sum(axis=-2)
    assert float(jnp.max(jnp.abs(rows - 1))) < 1e-5
    assert float(jnp.max(jnp.abs(cols - 1))) < 1e-5
    diag = np.asarray(jnp.diagonal(h_res, axis1=-2, axis2=-1))
    assert 0.3 < diag.mean() < 0.9 and float(jnp.std(h_res, axis=(0, 1))
                                             .mean()) > 0.01
    assert 0 < float(h_pre.min()) and float(h_pre.max()) < 1
    assert 0 < float(h_post.min()) and float(h_post.max()) < 2
    assert float(jnp.std(h_pre)) > 0.05 and float(jnp.std(h_post)) > 0.1
    # ONE round leaves the columns visibly off: the count matters
    _, _, once = ref.maps(x, hc, dict(CONFIG, hc_sinkhorn_iters=1))
    assert float(jnp.max(jnp.abs(once.sum(axis=-2) - 1))) > 0.02
    from ray_tpu.models.xing import HyperConnection

    (u, (post, res)), sown = HyperConnection(CFG).apply(
        {"params": hc}, x, mutable=["intermediates"])
    np.testing.assert_allclose(
        u, jnp.einsum("bti,btid->btd", h_pre, x), atol=1e-5)
    np.testing.assert_allclose(jnp.moveaxis(post, 0, -1), h_post,
                               atol=1e-6)
    np.testing.assert_allclose(          # [n, n, B, T] -> [B, T, n, n]
        jnp.transpose(res, (2, 3, 0, 1)), h_res, atol=1e-6)
    (err,) = sown["intermediates"]["residual"]
    np.testing.assert_allclose(
        err, [jnp.max(jnp.abs(rows - 1)), jnp.max(jnp.abs(cols - 1))],
        atol=1e-6)


def test_one_stream_with_neutral_maps_is_the_one_stream_block(params,
                                                              tokens):
    """``hc_mult`` 1 with every map at its neutral value (the gates 0,
    ``b_pre`` large so that ``H_pre`` = 1, ``b_post`` 0 so that ``H_post``
    = 2 sigmoid(0) = 1, ``H_res`` the 1 x 1 Sinkhorn of anything = 1)
    gives ``x + F(norm(x))``: Kimi-K2's model on the same weights."""
    one = dataclasses.replace(CFG, hc_mult=1)
    shapes = xing_init(one, jax.random.PRNGKey(7))

    def neutral(path, w):
        leaf = getattr(path[-1], "key", None)
        if leaf == "map_gate":
            return jnp.zeros_like(w)
        if leaf == "map_bias":
            return jnp.asarray([30.0, 0.0, 0.0], w.dtype)
        return w

    p1 = jax.tree_util.tree_map_with_path(neutral, _scaled(shapes))
    plain = {"params": {
        k: {n: w for n, w in v.items() if not n.endswith("_hc")}
        if k.startswith("layer_") else v for k, v in p1["params"].items()}}
    kimi = KimiK2Config.tiny(remat=False, n_layer=4, n_dense_layers=2,
                             routed_scaling_factor=2.0, rms_eps=1e-6)
    want = KimiK2(kimi).apply(plain, tokens)
    np.testing.assert_allclose(Xing(one).apply(p1, tokens), want, atol=2e-4)
    assert float(jnp.std(want)) > 0.05


# ------------------------------------------------ engine: the latent pool

PROMPTS = ([3, 17, 42, 99, 7, 250, 8], [9, 4] * 15 + [77], [5, 1, 200, 31])


def test_prefill_then_decode_equals_reference_through_the_latent_pool(
        params):
    """Three sequences of unequal length, each prefilled padded to its
    bucket (the padding's streams and maps change nothing before them),
    then decoded together in a batch of 6 rows of which two are empty
    (pages of 4 positions): at every generated position the logits equal
    the reference's full forward over prompt + generated tokens."""
    served, logits = kimi_faults.serve(CFG, params, PROMPTS, 8,
                                       max_batch=6, page=4)
    for prompt, toks, rows in zip(PROMPTS, served, logits):
        want = np.asarray(ref.forward(      # (a layer a jit: the faster)
            CONFIG, params, jnp.asarray([list(prompt) + toks[:-1]],
                                        jnp.int32), last=8, by_layer=True))[0]
        assert len(want) == len(rows) == 8
        np.testing.assert_allclose(np.stack(rows), want, atol=1e-4)


# --------------------------------------------------- the shares add up

def test_all_the_expert_shares_and_the_shared_expert_add_up(params):
    """The guide's test for a layer that MAY hold a share (the cell holds
    every expert, ``held_experts`` None; a deployment over more chips
    would not): four shares of 2 of the 8 experts on one sparse layer's
    input, the shared expert counted ONCE, add up to the uncut reference's
    whole FFN, and ``held_experts`` None computes that whole in one."""
    import flax.linen as nn

    from ray_tpu.models.kimi import ROUTE_NORM_EPS
    from ray_tpu.ops.moe import MoEMLP

    layer = params["params"]["layer_3"]
    h = jnp.asarray(np.random.default_rng(5).normal(size=(1, 23, 64)),
                    jnp.float32)
    flat = h.reshape(23, 64)
    common = dict(d_model=64, d_ff=32, num_experts=8, top_k=2, gated=True,
                  norm_topk_prob=True, scoring="sigmoid", select_bias=True,
                  norm_eps=ROUTE_NORM_EPS, routed_scaling_factor=2.0,
                  act=nn.silu, dtype=jnp.float32)
    parts = []
    for rank in range(4):
        moe = dict(layer["moe"])
        for name in ("w_gate", "w_up", "w_down"):
            moe[name] = moe[name][2 * rank:2 * rank + 2]
        parts.append(MoEMLP(first_expert=2 * rank, held_experts=2,
                            **common).apply({"params": moe}, h))
        assert float(jnp.max(jnp.abs(parts[-1]))) > 0
    shared = ref._swiglu(flat, *(layer[k]["kernel"] for k in (
        "shared_gate", "shared_up", "shared_down")))
    routed = ref._experts_eager(flat, layer["moe"], CONFIG)
    np.testing.assert_allclose(sum(parts).reshape(23, 64) + shared,
                               routed + shared, atol=2e-5)
    np.testing.assert_allclose(
        MoEMLP(**CFG.experts, d_model=64, gated=True, act=nn.silu,
               dtype=jnp.float32).apply({"params": layer["moe"]}, h)
        .reshape(23, 64), routed, atol=2e-5)
    assert CFG.experts["held_experts"] is None
    assert float(jnp.std(routed)) > 1e-2        # the routed part is live


# -------------------------------------------------------------- training

def test_loss_and_every_gradient_leaf_equal_the_reference(params, tokens):
    loss, grads = jax.jit(lambda p: jax.value_and_grad(
        lambda q: xing_loss_fn(CFG, q, {"tokens": tokens}))(p))(params)
    want, want_grads = jax.jit(
        lambda p: ref.loss_and_grads(CONFIG, p, tokens))(params)
    assert abs(float(loss) - float(want)) < 1e-5
    flat = jax.tree_util.tree_leaves_with_path(grads)
    flat_want = jax.tree_util.tree_leaves(want_grads)
    assert len(flat) == len(flat_want)
    for (path, g), w in zip(flat, flat_want):
        name = jax.tree_util.keystr(path)
        if name.endswith("['expert_bias']"):    # data: no gradient
            assert not np.asarray(g).any() and not np.asarray(w).any()
            continue
        assert float(jnp.max(jnp.abs(w))) > 0, name
        np.testing.assert_allclose(g, w, atol=2e-5, err_msg=name)


def test_adamw_decays_the_maps_matrices_and_not_their_biases_and_gates():
    from ray_tpu.train.train_step import _decayed

    shapes = jax.eval_shape(lambda: xing_init(CFG, jax.random.PRNGKey(0)))
    hc = _decayed(shapes)["params"]["layer_2"]["attn_hc"]
    assert hc == {"map_bias": False, "map_gate": False, "phi": True,
                  "norm": {"scale": True}}
    assert _decayed(shapes)["params"]["layer_2"]["moe"]["expert_bias"] \
        is False


# ------------------------------------------- the registry, engine, counters

def test_the_registry_builds_the_eighth_family():
    row = MODEL_FAMILIES["xing40"]
    assert len(MODEL_FAMILIES) == 10 and row.config is XingConfig
    assert family_of(row.tiny()).module is Xing
    # its config extends Kimi-K2's, whose own row still finds Kimi-K2's
    assert family_of(KimiK2Config.tiny()).module is KimiK2
    spec = row.cache(XingConfig())          # as published
    assert spec == CacheSpec(40, 0, 0, latent_dim=512, rope_dim=64)
    cut = XingConfig(n_layer=7)
    assert cut.n_moe_layers == 5 and cut.experts["held_experts"] is None
    assert cut.attention_params() == 28_409_856
    assert cut.hc_params() == 358_427
    params = jax.eval_shape(lambda: row.init(cut, jax.random.PRNGKey(0)))
    n = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert abs(n - 4.92e9) < 0.01e9
    whole = jax.eval_shape(lambda: row.init(XingConfig(),
                                            jax.random.PRNGKey(0)))
    assert abs(sum(a.size for a in jax.tree_util.tree_leaves(whole))
               - 29.5e9) < 0.1e9
    assert whole["params"]["layer_5"]["mlp_hc"]["phi"].dtype == jnp.float32
    from ray_tpu.train.distributed import rules_for_model

    assert rules_for_model("xing40") == row.partition_rules()
    with pytest.raises(ValueError, match="multi-token"):
        XingConfig(num_nextn_predict_layers=1)


def test_engine_counts_the_residual_path(params):
    """``stats()["residual"]``: the streams, the sublayers, the Sinkhorn's
    rounds and, from the last delivered program, how far any live row's
    ``H_res`` lay from doubly stochastic; a model of one stream has no
    such entry."""
    from ray_tpu.llm.engine import EngineConfig, GenerationEngine

    engine = GenerationEngine(
        model_cfg=CFG, params=params, engine_cfg=EngineConfig(
            page_size=4, num_pages=64, max_batch=2))
    assert list(engine.cache.paged) == ["latent_pages"]
    assert engine.stats()["residual"]["streams"] == 4
    seqs = [engine.submit(list(p), max_tokens=5) for p in PROMPTS[:2]]
    while not all(s.finished for s in seqs):
        engine.step()
    stats = engine.stats()
    assert stats["step_errors"] == 0, stats["last_error"]
    res = stats["residual"]
    assert (res["streams"], res["sublayers"], res["sinkhorn_iters"]) \
        == (4, 8, 20)
    assert 0 <= res["max_row_sum_err"] < 1e-5
    assert 0 <= res["max_col_sum_err"] < 1e-5
    assert stats["moe"]["layer_runs"] > 0
    greedy = [s.tokens[s.prompt_len:] for s in seqs]
    served, _ = kimi_faults.serve(CFG, params, PROMPTS[:2], 5, page=4)
    assert greedy == served
    # a later PR that trims the rounds shows here
    few = GenerationEngine(
        model_cfg=dataclasses.replace(CFG, hc_sinkhorn_iters=1),
        params=params, engine_cfg=EngineConfig(page_size=4, num_pages=64,
                                               max_batch=2))
    seq = few.submit(list(PROMPTS[0]), max_tokens=3)
    while not seq.finished:
        few.step()
    assert few.stats()["residual"]["max_col_sum_err"] > 1e-3
    assert "residual" not in GenerationEngine(model="kimik2").stats()


def test_the_lowered_forward_names_the_scopes_the_readers_file_by():
    """benchmark/harness/hc_phases.py files a trace's operations by
    ``hc.map``, ``hc.pre`` and ``hc.post``; the accepted readers by
    Kimi-K2's ``mla.*`` and ``moe.*`` names, which this family's programs
    have as that one's do."""
    from ray_tpu.llm.engine import jit_forward
    from ray_tpu.llm.kv_cache import init_pool, pages_for

    spec = MODEL_FAMILIES["xing40"].cache(CFG)
    params = jax.eval_shape(lambda: xing_init(CFG, jax.random.PRNGKey(0)))
    kv = jax.eval_shape(lambda: init_pool(spec, 16, 4, CFG.dtype))

    def lowered(shape):
        ints = jax.ShapeDtypeStruct(shape, jnp.int32)
        return jit_forward(Xing(CFG)).lower(
            params, ints, kv["latent_pages"], jax.ShapeDtypeStruct(
                (shape[0], pages_for(CFG.max_seq, 4)), jnp.int32),
            ints).as_text(debug_info=True)

    decode, prefill = lowered((2, 1)), lowered((1, 16))
    both = ("hc.map", "hc.pre", "hc.post", "mla.q", "mla.kv", "kv.store",
            "attn.out", "mlp/mlp.dense", "moe.shared", "moe.route",
            "moe.experts", "lm_head")
    for name in both + ("mla.absorb", "kv.attend"):
        assert name in decode, name
    for name in both + ("mla.expand",):
        assert name in prefill, name
    assert "mla.expand" not in decode and "mla.absorb" not in prefill
    # the FFN's write-back lies inside the block's ``mlp`` scope
    assert "mlp/hc.post" in decode and "layer_0/hc.post" in decode


# ----------------------------------- the comparison can tell right from wrong

@pytest.fixture(scope="module")
def served_right(params):
    prompts = [list(PROMPTS[1]), list(PROMPTS[0])]
    served, logits = kimi_faults.serve(CFG, params, prompts, 12, page=4)
    return prompts, served, logits


@pytest.mark.parametrize("name", xing_faults.FAULTS)
def test_each_fault_moves_the_served_logits(params, served_right, name):
    """The things the chip run holds to the cell's tolerance
    (benchmark/tools/xing_faults.py), here at the tiny size in float32,
    fed the right program's tokens: each moves some logit by far more
    than the ~1e-5 that separate the right program from the reference."""
    prompts, served, right = served_right
    with xing_faults.fault(name, CFG, params) as (cfg, p):
        _, wrong = kimi_faults.serve(cfg, p, prompts, 12, page=4,
                                     forced=served)
    apart = max(float(np.max(np.abs(np.stack(a) - np.stack(b))))
                for a, b in zip(right, wrong))
    assert apart > 1e-3, apart


def test_every_hook_of_the_fault_tool_is_on_a_name_that_exists():
    """The hooks patch ``models/xing.py``'s ``HC``, ``sinkhorn`` and
    ``RMSNorm`` and Kimi-K2's ``latent_attention``: a name that left would
    raise here and not inject nothing in silence; after a fault the names
    are what they were."""
    import ray_tpu.models.kimi as kimi
    import ray_tpu.models.xing as xing

    before = (xing.HC, xing.sinkhorn, xing.RMSNorm, kimi.latent_attention)
    for name in xing_faults.FAULTS[:-1]:    # (the last rewrites the tree)
        with xing_faults.fault(name, CFG, {"params": {}}):
            pass
        assert before == (xing.HC, xing.sinkhorn, xing.RMSNorm,
                          kimi.latent_attention), name
    with pytest.raises(ValueError):
        with xing_faults.fault("no_such_fault", CFG, {}):
            pass
