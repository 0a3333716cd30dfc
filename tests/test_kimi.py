"""Kimi-K2 (models/kimi.py: multi-head latent attention over a cache whose
row is one compressed vector and a rotary part, absorbed for a decode step
and expanded for a prefill; YaRN's rotary frequencies; a dense layer ahead
of a shared expert beside experts routed by sigmoid scores, a selection
bias and a scaling factor) held to its plain float32 reference
(benchmark/reference/kimi_k2_ref.py) at a tiny size on the CPU: one dense
and two sparse layers, 64 wide, 4 heads over ranks 32 | 24 and widths 16 |
8 | 16, top-2 of 8 experts of width 32, YaRN over 32 original positions.
Through the model, the engine's jitted forward with the latent pool, the
decode kernel in interpret mode, the two attention paths on the same
inputs, YaRN by hand, the expert shares, the loss and the family
registry."""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import kimi_k2_ref as ref
from benchmark.tools import kimi_faults
from ray_tpu.models import MODEL_FAMILIES, CacheSpec, family_of
from ray_tpu.models.kimi import (KimiK2, KimiK2Config, kimi_k2_init,
                                 kimi_k2_loss_fn)
from ray_tpu.models.layers import yarn_inv_freq, yarn_mscale

CFG = KimiK2Config.tiny(remat=False)
# The reference's configuration: the source's keys at the tiny size.
CONFIG = {"num_hidden_layers": 3, "hidden_size": 64,
          "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 24,
          "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
          "first_k_dense_replace": 1, "n_routed_experts": 8,
          "n_shared_experts": 1, "num_experts_per_tok": 2,
          "norm_topk_prob": True, "scoring_func": "sigmoid",
          "n_group": 1, "topk_group": 1, "routed_scaling_factor": 2.827,
          "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
          "rope_scaling": {"type": "yarn", "factor": 4.0,
                           "original_max_position_embeddings": 32,
                           "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                           "mscale_all_dim": 1}}


def _scaled(params, factor=8.0):
    """std-0.02 weights at 64 wide leave every router near-uniform and
    every softmax flat; scaled up, routing and attention are decided and
    an error of the mathematics shows (tests/test_olmoe.py).  The 1-D
    leaves (norm scales, expert_bias) stay as drawn."""
    return jax.tree_util.tree_map(
        lambda w: w if w.ndim == 1 else factor * w, params)


@pytest.fixture(scope="module")
def params():
    return _scaled(kimi_k2_init(CFG, jax.random.PRNGKey(7)))


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 45)),
                       jnp.int32)


# ------------------------------------------------ forward against reference

def test_forward_equals_reference(params, tokens):
    """The full (expanded) forward against the reference over 45
    positions, past YaRN's 32 original ones; logits of size ~1; and the
    selection bias is live: without it the logits move."""
    want = ref.forward(CONFIG, params, tokens)
    assert float(jnp.std(want)) > 0.05
    got = jax.jit(lambda p, t: KimiK2(CFG).apply(p, t))(params, tokens)
    np.testing.assert_allclose(got, want, atol=5e-5)
    with kimi_faults.fault("no_select_bias", CFG, params) as (_, unbiased):
        moved = ref.forward(CONFIG, unbiased, tokens)
    assert float(jnp.max(jnp.abs(moved - want))) > 1e-2


def test_the_reference_in_blocks_equals_the_reference_whole(params, tokens,
                                                            monkeypatch):
    """``forward`` attends in blocks of query positions so that 4,096
    fit: blocks of 16 over 45 positions (a ragged last one) give what one
    block gives."""
    whole = ref.forward(CONFIG, params, tokens)
    monkeypatch.setattr(ref, "ATTN_BLOCK", 16)
    np.testing.assert_allclose(ref.forward(CONFIG, params, tokens), whole,
                               atol=1e-5)


# --------------------------------------- engine: the latent pool, both paths

PROMPTS = ([3, 17, 42, 99, 7, 250, 8], [9, 4] * 15 + [77], [5, 1, 200, 31])


def _against_reference(params, served, logits, n=8):
    for prompt, toks, rows in zip(PROMPTS, served, logits):
        want = np.asarray(ref.forward(
            CONFIG, params, jnp.asarray([list(prompt) + toks[:-1]],
                                        jnp.int32)))[0][len(prompt) - 1:]
        assert len(want) == len(rows) == n
        np.testing.assert_allclose(np.stack(rows), want, atol=1e-4)


def test_prefill_then_decode_equals_reference_through_the_latent_pool(
        params):
    """Three sequences of unequal length, each prefilled padded to its
    bucket (7 -> 8, 31 -> 32, 4 -> 8 positions: the EXPANDED path, which
    stores latent rows into pages that held other numbers), then decoded
    together in a batch of 6 rows of which row 1 and row 5 are empty (the
    ABSORBED path over the pages, pages of 4 positions: every sequence
    crosses page boundaries while it decodes): at every generated
    position the logits equal the reference's full forward over prompt +
    generated tokens.  A larger batch with more padding gives the
    same."""
    served, logits = kimi_faults.serve(CFG, params, PROMPTS, 8,
                                       max_batch=6, page=4)
    _against_reference(params, served, logits)
    served9, logits9 = kimi_faults.serve(CFG, params, PROMPTS, 8,
                                         max_batch=9, page=4)
    assert served9 == served
    for a, b in zip(logits, logits9):
        np.testing.assert_allclose(np.stack(a), np.stack(b), atol=1e-6)


def test_the_decode_kernel_serves_the_same_across_its_block_boundaries(
        params, monkeypatch):
    """The same three sequences with the decode step through the Pallas
    kernel (interpret mode; blocks of 2 pages of 4 = 8 positions, so the
    31-token prompt's decode crosses the block boundary at 32 and every
    sequence reads a ragged last block), held to the reference."""
    import ray_tpu.models.attention as attention
    from ray_tpu.ops import paged_attention

    monkeypatch.setattr(attention, "_latent_kernel",
                        lambda q_lat, pages: q_lat.shape[1] == 1)
    monkeypatch.setattr(
        paged_attention, "paged_decode_latent", functools.partial(
            paged_attention.paged_decode_latent, block_pages=2,
            interpret=True))
    served, logits = kimi_faults.serve(CFG, params, PROMPTS, 8,
                                       max_batch=6, page=4)
    _against_reference(params, served, logits)


def _projected(seed, b, t, h=4, d_n=16, d_r=8, r=24, d_v=16):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    return (f(b, t, h, d_n), f(b, t, h, d_r), f(b, t, r), f(b, t, d_r),
            f(r, h, d_n + d_v))


def test_absorbed_attention_equals_expanded_attention():
    """``latent_attention``'s two paths on the same inputs: 2 sequences
    of 13 positions attended whole (expanded, no cache), against the same
    positions fed one at a time through a latent pool (absorbed: stored,
    then attended in the latent space), position by position."""
    from ray_tpu.llm.kv_cache import init_pool
    from ray_tpu.models.attention import latent_attention

    b, t = 2, 13
    q_nope, q_pe, c_kv, k_pe, w_kvb = _projected(3, b, t)
    whole, none = latent_attention(CFG, q_nope, q_pe, c_kv, k_pe, w_kvb,
                                   0.3)
    assert none is None and whole.shape == (b, t, 4, 16)
    spec = CacheSpec(1, 0, 0, latent_dim=24, rope_dim=8)
    pages = init_pool(spec, 8, 4, jnp.float32)["latent_pages"] + 1.0
    table = jnp.arange(8, dtype=jnp.int32).reshape(2, 4)
    for p in range(t):
        at = slice(p, p + 1)
        step, pages = latent_attention(
            CFG, q_nope[:, at], q_pe[:, at], c_kv[:, at], k_pe[:, at],
            w_kvb, 0.3, cache={
                "latent_pages": pages, "layer": 0, "page_table": table,
                "positions": jnp.full((b, 1), p, jnp.int32)})
        np.testing.assert_allclose(step, whole[:, at], atol=2e-5)
    # a row is [c_kv | k_pe | zeros up to 128 lanes]
    row = np.asarray(pages[0, 0, 1])
    np.testing.assert_allclose(row[:24], c_kv[0, 1], atol=1e-6)
    np.testing.assert_allclose(row[24:32], k_pe[0, 1], atol=1e-6)
    assert not row[32:].any() and row.shape == (128,)


@pytest.mark.parametrize("lengths", [(15, 16, 17, 0), (33, 1, 48, 32)])
def test_latent_decode_kernel_equals_its_definition(lengths):
    """``paged_decode_latent`` (interpret mode) against ``latent_attend``:
    8 heads over rows of 128 + 64 -> 256 lanes, pages of 8 in blocks of 2
    (16 positions), lengths on, before and after block boundaries, a row
    of length 0 (zeros out), page tables that are no identity, a pool
    that holds other numbers everywhere."""
    from ray_tpu.llm.kv_cache import latent_attend
    from ray_tpu.ops.paged_attention import paged_decode_latent

    rng = np.random.default_rng(11)
    b, h, r, d_r, page, per_seq = 4, 8, 128, 64, 8, 6
    pages = jnp.asarray(rng.normal(size=(2, b * per_seq, page, 256)),
                        jnp.float32)
    table = jnp.asarray(rng.permutation(b * per_seq).reshape(b, per_seq),
                        jnp.int32)
    q_lat = jnp.asarray(rng.normal(size=(b, 1, h, r)), jnp.float32)
    q_pe = jnp.asarray(rng.normal(size=(b, 1, h, d_r)), jnp.float32)
    lens = jnp.asarray(lengths, jnp.int32)
    got = paged_decode_latent(q_lat, q_pe, pages, 1, table, lens,
                              scale=0.09, block_pages=2, interpret=True)
    want = latent_attend(q_lat, q_pe, pages, 1, table, lens[:, None] - 1,
                         0.09)
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(got[live], want[live], atol=2e-5)
    assert not np.asarray(got[~live]).any()


# ---------------------------------------------------------- YaRN, by hand

def test_yarn_frequencies_and_mscale_equal_numbers_worked_out_by_hand():
    """The published rotary part: 64 dimensions, theta 50000, factor 64
    over 4096 original positions, beta_fast 32, beta_slow 1.  The
    correction dimensions: 64 ln(4096 / (32 x 2 pi)) / (2 ln 50000) =
    8.91 -> 8 and 64 ln(4096 / (2 pi)) / (2 ln 50000) = 19.16 -> 20, so
    pairs 0..8 keep theta's frequency, pairs 20..31 get a 64th of it,
    and pair 14, half way up the ramp, (1/64 + 1) / 2 of it.  mscale =
    0.1 ln 64 + 1 = 1.41589; the softmax scale 192 ** -0.5 x mscale ** 2
    = 0.144680."""
    inv = yarn_inv_freq(64, 50000.0, 64.0, 4096, 32.0, 1.0)
    f = 50000.0 ** (-np.arange(32) / 32.0)
    assert math.floor(64 * math.log(4096 / (32 * 2 * math.pi))
                      / (2 * math.log(50000))) == 8
    assert math.ceil(64 * math.log(4096 / (2 * math.pi))
                     / (2 * math.log(50000))) == 20
    np.testing.assert_allclose(inv[:9], f[:9], rtol=1e-6)
    np.testing.assert_allclose(inv[20:], f[20:] / 64, rtol=1e-6)
    np.testing.assert_allclose(inv[14], f[14] * 0.5078125, rtol=1e-6)
    np.testing.assert_allclose(inv[11], f[11] * (0.75 + 0.25 / 64),
                               rtol=1e-6)
    np.testing.assert_allclose(
        inv, ref.yarn_inv_freq({
            "qk_rope_head_dim": 64, "rope_theta": 50000, "rope_scaling": {
                "type": "yarn", "factor": 64, "beta_fast": 32,
                "beta_slow": 1, "original_max_position_embeddings": 4096}}),
        rtol=1e-6)
    assert abs(yarn_mscale(64.0, 1.0) - 1.4158883) < 1e-6
    assert yarn_mscale(1.0, 1.0) == 1.0
    assert abs(KimiK2Config().softmax_scale - 0.144680) < 1e-6
    with pytest.raises(ValueError, match="mscale"):
        KimiK2Config(rope_mscale=0.5)


# --------------------------------------------------- the shares add up

def test_all_the_expert_shares_and_the_shared_expert_add_up(params):
    """Expert parallelism over four chips of two experts each (the
    benchmark's cut is 32 chips of 12 of 384), on one layer's input: the
    routed parts the four shares compute (ops/moe.py told which experts
    it holds; the weights renormalised over the 8 CHOSEN, held here or
    not, times ``routed_scaling_factor``) plus the shared expert counted
    ONCE equal the uncut reference's whole FFN; and the reference given a
    share computes that share."""
    import flax.linen as nn

    from ray_tpu.models.kimi import ROUTE_NORM_EPS
    from ray_tpu.ops.moe import MoEMLP

    layer = params["params"]["layer_2"]
    h = jnp.asarray(np.random.default_rng(5).normal(size=(1, 23, 64)),
                    jnp.float32)
    flat = h.reshape(23, 64)
    parts = []
    for rank in range(4):
        moe = dict(layer["moe"])
        for name in ("w_gate", "w_up", "w_down"):
            moe[name] = moe[name][2 * rank:2 * rank + 2]
        op = MoEMLP(d_model=64, d_ff=32, num_experts=8, top_k=2, gated=True,
                    norm_topk_prob=True, scoring="sigmoid", select_bias=True,
                    norm_eps=ROUTE_NORM_EPS, routed_scaling_factor=2.827,
                    act=nn.silu, dtype=jnp.float32, first_expert=2 * rank,
                    held_experts=2)
        y, sown = op.apply({"params": moe}, h, mutable=["intermediates"])
        (m,) = sown["intermediates"]["moe"]
        parts.append((y, int(jnp.sum(m["load"]))))
        share = dict(CONFIG, n_routed_experts=2, first_routed_expert=2 * rank)
        np.testing.assert_allclose(
            y.reshape(23, 64), ref._experts_eager(flat, moe, share),
            atol=2e-5)
    assert sum(n for _, n in parts) == 23 * 2       # every pair, once
    assert all(float(jnp.max(jnp.abs(y))) > 0 for y, _ in parts)
    shared = ref._swiglu(flat, *(layer[k]["kernel"] for k in (
        "shared_gate", "shared_up", "shared_down")))
    want = ref._experts_eager(flat, layer["moe"], CONFIG) + shared
    got = sum(y for y, _ in parts).reshape(23, 64) + shared
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert float(jnp.std(want - shared)) > 1e-2     # the routed part is live


# -------------------------------------------------------------- training

def test_loss_and_every_gradient_leaf_equal_the_reference(params, tokens):
    loss, grads = jax.jit(lambda p: jax.value_and_grad(
        lambda q: kimi_k2_loss_fn(CFG, q, {"tokens": tokens}))(p))(params)
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


def test_flash_training_forward_pads_v_to_the_keys_width(params, tokens):
    """``attn_impl="flash"`` (interpret mode here): keys of 24 against
    values of 16 go through the kernel with v padded, and give the dense
    forward's logits."""
    dense = KimiK2(CFG).apply(params, tokens[:, :32])
    flash = KimiK2(dataclasses.replace(CFG, attn_impl="flash")).apply(
        params, tokens[:, :32])
    np.testing.assert_allclose(flash, dense, atol=5e-5)


# ------------------------------------------- the registry, engine, counters

def test_the_registry_builds_the_sixth_family():
    row = MODEL_FAMILIES["kimik2"]
    assert len(MODEL_FAMILIES) == 10 and row.config is KimiK2Config
    assert family_of(row.tiny()).module is KimiK2
    spec = row.cache(KimiK2Config())        # as published
    assert spec == CacheSpec(61, 0, 0, latent_dim=512, rope_dim=64)
    assert spec.row_width == 640            # 576 numbers in 5 tiles
    assert CacheSpec(1, 8, 64).row_width == 0
    cut = KimiK2Config(vocab_size=20480, n_layer=7, held_experts=12)
    assert cut.n_moe_layers == 6
    assert cut.attention_params() == 101_122_048
    params = jax.eval_shape(lambda: row.init(cut, jax.random.PRNGKey(0)))
    n = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert abs(n - 4.85e9) < 0.01e9
    from ray_tpu.train.distributed import rules_for_model

    assert rules_for_model("kimi_k2") == row.partition_rules()


def test_engine_holds_one_latent_pool_and_counts_what_a_row_is(params):
    """The engine builds the ONE array the spec names (no V pool), says
    what a position of a layer occupies, padding included, with the
    latent and rope widths beside it, and counts what the prefill buckets
    computed beside the prompt tokens asked for."""
    from ray_tpu.llm.engine import EngineConfig, GenerationEngine

    engine = GenerationEngine(
        model_cfg=CFG, params=params, engine_cfg=EngineConfig(
            page_size=4, num_pages=64, max_batch=2))
    assert list(engine.cache.paged) == ["latent_pages"]
    assert engine.cache.paged["latent_pages"].shape == (3, 64, 4, 128)
    seqs = [engine.submit(list(p), max_tokens=5) for p in PROMPTS[:2]]
    while not all(s.finished for s in seqs):
        engine.step()
    stats = engine.stats()
    assert stats["step_errors"] == 0, stats["last_error"]
    att = stats["attention"]
    assert att["kv_row_bytes"] == 128 * 4       # float32 here
    assert (att["latent_dim"], att["rope_dim"]) == (24, 8)
    assert stats["prefill_tokens"] == 7 + 31
    assert stats["prefill_bucket_tokens"] == 8 + 32
    assert "state" not in stats and stats["moe"]["layer_runs"] > 0
    greedy = [s.tokens[s.prompt_len:] for s in seqs]
    served, _ = kimi_faults.serve(CFG, params, PROMPTS[:2], 5, page=4)
    assert greedy == served
    other = GenerationEngine(model="gpt2").stats()
    assert "latent_dim" not in other["attention"]
    assert other["prefill_bucket_tokens"] == 0


def test_the_lowered_forward_names_the_scopes_the_readers_file_by():
    """benchmark/harness/mla_phases.py files a trace's operations by
    these names: a decode step has ``mla.q``, ``mla.kv``, ``mla.absorb``
    around ``kv.attend``, ``kv.store`` and ``attn.out``; a prefill has
    ``mla.expand`` and no ``mla.absorb`` and no ``kv.attend`` (it reads
    nothing from the pool); both have ``moe.shared`` and ``mlp.dense``."""
    from ray_tpu.llm.engine import jit_forward
    from ray_tpu.llm.kv_cache import init_pool, pages_for

    spec = MODEL_FAMILIES["kimik2"].cache(CFG)
    params = jax.eval_shape(lambda: kimi_k2_init(CFG, jax.random.PRNGKey(0)))
    kv = jax.eval_shape(lambda: init_pool(spec, 16, 4, CFG.dtype))

    def lowered(shape):
        ints = jax.ShapeDtypeStruct(shape, jnp.int32)
        return jit_forward(KimiK2(CFG)).lower(
            params, ints, kv["latent_pages"], jax.ShapeDtypeStruct(
                (shape[0], pages_for(CFG.max_seq, 4)), jnp.int32),
            ints).as_text(debug_info=True)

    decode, prefill = lowered((2, 1)), lowered((1, 16))
    both = ("mla.q", "mla.kv", "kv.store", "attn.out", "mlp/mlp.dense",
            "moe.shared", "moe.route", "moe.experts", "lm_head")
    for name in both + ("mla.absorb", "kv.attend"):
        assert name in decode, name
    for name in both + ("mla.expand",):
        assert name in prefill, name
    assert "mla.expand" not in decode
    assert "mla.absorb" not in prefill and "kv.attend" not in prefill
    assert not any("kv.attend" in x and "mla.absorb" in x
                   for x in decode.splitlines())


# ----------------------------------- the comparison can tell right from wrong

@pytest.fixture(scope="module")
def served_right(params):
    prompts = [list(PROMPTS[1]), list(PROMPTS[0])]
    served, logits = kimi_faults.serve(CFG, params, prompts, 12, page=4)
    return prompts, served, logits


@pytest.mark.parametrize("name", kimi_faults.FAULTS)
def test_each_fault_moves_the_served_logits(params, served_right, name):
    """The things the chip run holds to the cell's tolerance
    (benchmark/tools/kimi_faults.py), here at the tiny size in float32,
    fed the right program's tokens: each moves some logit by far more
    than the ~1e-5 that separate the right program from the reference.
    (Blocks of 8 positions for the pages read short: the 31-token prompt
    passes 32 while it decodes.)"""
    prompts, served, right = served_right
    with kimi_faults.fault(name, CFG, params, block_rows=8) as (cfg, p):
        _, wrong = kimi_faults.serve(cfg, p, prompts, 12, page=4,
                                     forced=served)
    apart = max(float(np.max(np.abs(np.stack(a) - np.stack(b))))
                for a, b in zip(right, wrong))
    assert apart > 1e-3, apart
