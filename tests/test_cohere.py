"""Command A+ (models/cohere.py: sliding-window layers with RoPE over
adjacent pairs, which keep a ring of ``window`` positions a sequence in the
SECOND group of the K/V pool, beside a full-attention layer without a
position encoding in the first; a parallel attention + experts block under
ONE LayerNorm; shared experts averaged; a tied head) held to its plain
float32 reference (benchmark/reference/cohere2_moe_ref.py) at a tiny size
on the CPU: one period of three and one, 64 wide, 4 heads of 32 on 2 K/V
heads, a window of 8, top-2 of 8 experts beside 2 shared ones.  Through the
model, the engine's jitted forward with BOTH groups, the engine itself,
each fault of benchmark/tools/command_a_faults.py, the expert shares, the
loss and the family registry."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import cohere2_moe_ref as ref
from benchmark.tools import command_a_faults as faults
from ray_tpu.models import MODEL_FAMILIES, CacheSpec, family_of
from ray_tpu.models.cohere import (FULL, SLIDING, Cohere2Moe,
                                   Cohere2MoeConfig, cohere2_moe_init,
                                   cohere2_moe_loss_fn)

CFG = Cohere2MoeConfig.tiny(remat=False)
# The reference's configuration: the source's keys at the tiny size.
CONFIG = {"model_type": "cohere2_moe", "num_hidden_layers": 4,
          "hidden_size": 64, "layer_types": list(CFG.layer_types),
          "num_attention_heads": 4, "num_key_value_heads": 2,
          "head_dim": 32, "hidden_act": "silu", "attention_bias": False,
          "layer_norm_eps": 1e-5, "tie_word_embeddings": True,
          "use_parallel_block": True, "use_qk_norm": False,
          "expert_selection_fn": "sigmoid", "norm_topk_prob": True,
          "rotary_pct": 1, "position_embedding_type": "rope_gptj",
          "shared_expert_combination_strategy": "average",
          "first_k_dense_replace": 0, "use_gated_activation": True,
          "intermediate_size": 32, "num_experts": 8,
          "num_experts_per_tok": 2, "num_shared_experts": 2,
          "sliding_window": 8, "rope_theta": 10000.0, "logit_scale": 1,
          "vocab_size": 256}
# Shorter than the window of 8, as long, one longer, and several windows
# long (across page and ring boundaries at pages of 4: a ring is 2 pages).
PROMPTS = [tuple(range(3, 8)), tuple(range(40, 48)), tuple(range(90, 99)),
           tuple(range(120, 151)), (200, 7, 91), tuple(range(10, 55))]


def _scaled(params, factor=4.0):
    """std-0.02 weights at 64 wide leave every softmax flat and every
    router score near a half; scaled up, attention and the router are
    decided and an error of the mathematics shows.  The norms' scales stay
    as drawn."""
    return jax.tree_util.tree_map(
        lambda w: w if w.ndim == 1 else factor * w, params)


@pytest.fixture(scope="module")
def params():
    return _scaled(cohere2_moe_init(CFG, jax.random.PRNGKey(7)))


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 29)),
                       jnp.int32)


# ------------------------------------------------ forward against reference

def test_forward_equals_reference(params, tokens):
    """29 positions under a window of 8: the dense definition's band, RoPE
    over adjacent pairs in three layers and none in the fourth, ONE
    LayerNorm under both sublayers, the shared experts' mean."""
    got = Cohere2Moe(CFG).apply(params, tokens)
    want = ref.forward(CONFIG, params, tokens)
    assert float(jnp.std(want)) > 0.3
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_reference_a_layer_a_jit_equals_its_eager_form(params, tokens,
                                                           monkeypatch):
    """The reference's blocks of query positions (5 does not divide 29) and
    its masked experts under ``jit`` give the eager sums."""
    whole = ref.forward(CONFIG, params, tokens)
    monkeypatch.setattr(ref, "ATTN_BLOCK", 5)
    ref._compiled_layer.cache_clear()
    np.testing.assert_allclose(ref.forward(CONFIG, params, tokens), whole,
                               atol=2e-5)
    by_layer = ref.forward(CONFIG, params, tokens, last=3, lengths=[29, 20],
                           by_layer=True)
    ref._compiled_layer.cache_clear()
    np.testing.assert_allclose(by_layer[0], whole[0, -3:], atol=2e-5)
    np.testing.assert_allclose(by_layer[1], whole[1, 17:20], atol=2e-5)


def test_loss_and_gradients_equal_the_references(params, tokens):
    """The trainer's path (``next_token_loss``, dense) masks the band."""
    loss, grads = jax.value_and_grad(
        lambda p: cohere2_moe_loss_fn(CFG, p, {"tokens": tokens}))(params)
    want, want_grads = ref.loss_and_grads(CONFIG, params, tokens)
    assert abs(float(loss) - float(want)) < 1e-5
    flat, wanted = (jax.tree_util.tree_leaves_with_path(g)
                    for g in (grads, want_grads))
    for (path, g), (_, w) in zip(flat, wanted, strict=True):
        np.testing.assert_allclose(g, w, atol=3e-5, err_msg=str(path))


def test_a_window_never_trains_as_a_full_triangle_in_silence(params, tokens):
    """The flash kernel's backward knows no window: asked for without a
    cache it raises; so do the context-parallel forms."""
    for impl in ("flash", "ring"):
        cfg = dataclasses.replace(CFG, attn_impl=impl)
        with pytest.raises(ValueError, match="window"):
            Cohere2Moe(cfg).apply(params, tokens)


# ------------------------------------------- through the engine's programs

def _against_reference(params, prompts, served, logits, n):
    for prompt, toks, rows in zip(prompts, served, logits):
        want = np.asarray(ref.forward(
            CONFIG, params, jnp.asarray([list(prompt) + toks[:-1]],
                                        jnp.int32)))[0][len(prompt) - 1:]
        assert len(want) == len(rows) == n
        np.testing.assert_allclose(np.stack(rows), want, atol=1e-4)


@pytest.mark.parametrize("page", [4, 16], ids=["ring_of_2_pages",
                                               "ring_inside_a_page"])
def test_prefill_then_decode_equals_reference_through_both_groups(params,
                                                                  page):
    """Six sequences of 5, 8, 9, 31, 3 and 45 positions (shorter than the
    window of 8, as long, one longer, several windows long), each prefilled
    padded to its bucket (the band among its own rows, the last 8 real rows
    stored into a ring that held other numbers, every row into the full
    layer's pages), then decoded together in a batch of 9 rows of which
    two are empty, 14 tokens: every ring wraps, rows of different lengths
    share a step, and write positions pass page and ring boundaries.  At
    every generated position the logits equal the reference's full forward
    over prompt + generated tokens.  A ring is 2 pages of 4, or 8 rows of
    one page of 16."""
    served, logits = faults.serve(CFG, params, PROMPTS, 14, max_batch=9,
                                  page=page)
    _against_reference(params, PROMPTS, served, logits, 14)


def test_the_paged_kernel_reads_the_rings_at_16_query_heads_a_group(
        monkeypatch):
    """The decode kernel (interpreted), unedited, over BOTH groups: the
    rings handed ``positions mod window`` and ``min(length, window)``; 32
    query heads on 2 K/V heads of 64, the cell's 16 a group, a folded row
    of 128 lanes; logits equal the gather's, which the reference holds."""
    import ray_tpu.models.attention as attention
    from ray_tpu.ops import paged_attention

    cfg = Cohere2MoeConfig.tiny(remat=False, n_head=32, head_dim=64)
    params = _scaled(cohere2_moe_init(cfg, jax.random.PRNGKey(5)))
    plain = faults.serve(cfg, params, PROMPTS[1:4], 12, max_batch=5,
                         page=8)
    calls = []
    real = paged_attention.paged_decode

    def spy(q, k_pages, *args, **kwargs):
        calls.append((q.shape, k_pages.shape[0]))
        return real(q, k_pages, *args, **kwargs)

    monkeypatch.setattr(attention, "_decode_kernel",
                        paged_attention.supported)
    monkeypatch.setattr(paged_attention, "paged_decode", spy)
    served, logits = faults.serve(cfg, params, PROMPTS[1:4], 12,
                                  max_batch=5, page=8)
    # (traced once a call site: three window layers, then the full one)
    assert calls == [((5, 1, 32, 64), 3)] * 3 + [((5, 1, 32, 64), 1)]
    assert served == plain[0]
    for a, b in zip(logits, plain[1]):
        np.testing.assert_allclose(np.stack(a), np.stack(b), atol=2e-5)


@pytest.fixture(scope="module")
def as_it_is(params):
    return faults.serve(CFG, params, PROMPTS[2:5], 10, max_batch=5, page=4)


@pytest.mark.parametrize("name", faults.FAULTS)
def test_each_fault_moves_the_logits(params, as_it_is, name):
    """Every fault of benchmark/tools/command_a_faults.py, served the right
    program's tokens, moves some generated position's logits by far more
    than the program lies from its reference (1e-4); and the patch is
    undone after it."""
    served, logits = as_it_is
    with faults.fault(name, CFG, params) as (cfg, p):
        _, wrong = faults.serve(cfg, p, PROMPTS[2:5], 10, max_batch=5,
                                page=4, forced=served)
    apart = max(float(np.max(np.abs(np.stack(a) - np.stack(b))))
                for a, b in zip(logits, wrong))
    assert apart > 0.05, (name, apart)
    _, again = faults.serve(CFG, params, PROMPTS[2:3], 3, max_batch=5,
                            page=4)
    np.testing.assert_allclose(np.stack(again[0]), np.stack(logits[0][:3]),
                               atol=1e-6)


def _engine(params, cfg=CFG, **engine):
    from ray_tpu.llm.engine import EngineConfig, GenerationEngine

    return GenerationEngine(
        model_cfg=cfg, params=params,
        engine_cfg=EngineConfig(**{**dict(page_size=4, num_pages=64,
                                          max_batch=2), **engine}))


def _run(engine, *requests):
    seqs = [engine.submit(list(p), max_tokens=n) for p, n in requests]
    while not all(s.finished for s in seqs):
        engine.step()
    assert engine.stats()["step_errors"] == 0, engine.stats()["last_error"]
    return [s.tokens[s.prompt_len:] for s in seqs]


def _gauge(name, group):
    from ray_tpu.util.metrics import registry

    for snap in registry().snapshot():
        if snap["name"] == name:
            return {s["tags"].get("group"): s["value"]
                    for s in snap["series"]}[group]
    raise AssertionError(f"gauge {name} not published")


def test_the_engine_serves_it_through_both_groups(params):
    """The K/V pool in TWO groups: arrays, page counts, tables and host
    allocators by group; a sequence takes its pages AND its whole ring at
    admission and gives both back; the window group's pages follow from
    ``max_batch`` and the window; the engine's stream is the jitted
    forward's (and so the reference's); an eviction's re-prefill reproduces
    it; stats() count what each group's layers read and hold."""
    engine = _engine(params, max_batch=4)
    assert list(engine.cache.paged) == ["k_pages", "v_pages", "window_k_pages",
                                "window_v_pages"]
    assert engine.cache.paged["k_pages"].shape == (1, 64, 4, 64)
    assert engine.cache.paged["window_k_pages"].shape == (3, 4 * 2, 4, 64)
    assert engine.cache.pools["window"].num_pages == 8 \
        and engine.cache.ring_pages == 2
    assert _gauge("rt_llm_kv_pages_total", "window") == 8.0
    assert _gauge("rt_llm_kv_pages_total", "full") == 64.0
    requests = ((PROMPTS[0], 9), (PROMPTS[3], 9))
    seqs = [engine.submit(list(p), max_tokens=n) for p, n in requests]
    engine.step()
    assert [len(s.held.ring) for s in seqs] == [2, 2]
    assert engine.stats()["kv_pages"]["window"] == {"used": 4, "total": 8}
    assert _gauge("rt_llm_kv_pages_used", "window") == 4.0
    while not all(s.finished for s in seqs):
        engine.step()
    out = [s.tokens[s.prompt_len:] for s in seqs]
    served, _ = faults.serve(CFG, params, [PROMPTS[0], PROMPTS[3]], 9,
                             page=4)
    assert out == served
    stats = engine.stats()
    assert stats["kv_pages"] == {"full": {"used": 0, "total": 64},
                                 "window": {"used": 0, "total": 8}}
    att = stats["attention"]
    assert att["decode_runs"] == 8                  # 9 tokens: 1 + 8 steps
    assert (att["window"], att["window_layers"]) == (8, 3)
    assert att["kv_row_bytes"] == 2 * 64 * 4        # K and V, float32 here
    # by hand: rows of 5 and 31 positions, 8 steps, pages of 4.  The full
    # layer reads ceil((n + 1) / 4) pages a row a step, a window layer
    # ceil(min(n + 1, 8) / 4): the short row 2 pages at 5..7 cached, then
    # its ring of 2; the long one its ring of 2 throughout.
    full = sum(-(-(n + 1) // 4) for first in (5, 31)
               for n in range(first, first + 8))
    ring = sum(-(-min(n + 1, 8) // 4) for first in (5, 31)
               for n in range(first, first + 8))
    assert ring == 2 * 16
    assert att["window_rows_read"] == ring * 4 * 3
    assert att["kv_rows_read"] == full * 4 * 1 + ring * 4 * 3
    assert att["window_positions_dropped"] == (full - ring) * 4 * 3
    per_seq = engine.cache.pages_per_seq
    assert att["window_rows_held"] == 8 * 4 * 2 * 4 * 3
    assert att["kv_rows_held"] == 8 * 4 * (per_seq * 4 + 2 * 4 * 3)
    # an eviction gives the ring back with the pages, and the re-prefill
    # (from position 0, through the band) reproduces the stream
    requests = ((PROMPTS[0], 20), (PROMPTS[4], 20))
    tight = _engine(params, num_pages=10)
    out = _run(tight, *requests)
    assert tight.stats()["evictions"] > 0
    assert out == _run(_engine(params), *requests)
    assert [p.used for p in tight.cache.pools.values()] == [0, 0]


def test_a_prefill_that_does_not_start_at_position_0_is_refused(params):
    """A multi-row step attends among its own rows and a window layer's
    store keeps its last ``window`` rows on that assumption: the engine
    refuses, on the host, to build one from another position."""
    engine = _engine(params)
    seq = engine.submit(list(PROMPTS[0]), max_tokens=4)
    engine._waiting.clear()
    assert engine.cache.take(seq.held, len(seq.tokens))
    seq.n_cached = 3
    with pytest.raises(ValueError, match="starts at position 0, not 3"):
        engine._prefill(seq)


def test_a_spec_without_window_layers_builds_the_parents_arrays():
    """One group, as ever: the same arrays, no second pool, no second
    table, and the forward's arguments under the parent's names."""
    import inspect

    from ray_tpu.llm.engine import GenerationEngine, jit_forward
    from ray_tpu.llm.kv_cache import (init_pool, pool_arrays, pool_tables,
                                      ring_pages)

    for name in ("llama", "granitemoehybrid", "olmohybrid", "kimik2"):
        fam = MODEL_FAMILIES[name]
        cfg = fam.tiny()
        spec = fam.cache(cfg)
        assert (spec.window_layers, spec.window) == (0, 0)
        assert pool_tables(spec) == ("page_table",)
        assert ring_pages(spec, 4) == 0
        pool = init_pool(spec, 16, 4, cfg.dtype)
        assert tuple(pool) == pool_arrays(spec)
        assert not any(k.startswith("window_") for k in pool)
    engine = GenerationEngine("llama")
    assert list(engine.cache.pools) == ["full"]
    assert "kv_pages" not in engine.stats()
    assert "window" not in engine.stats()["attention"]
    names = list(inspect.signature(jit_forward(
        engine._model).__wrapped__).parameters)
    assert names[:6] == ["p", "tokens", "k_pages", "v_pages", "page_table",
                         "positions"]
    spec = MODEL_FAMILIES["cohere2moe"].cache(CFG)
    assert pool_arrays(spec) == ("k_pages", "v_pages", "window_k_pages",
                                 "window_v_pages")
    assert pool_tables(spec) == ("page_table", "window_table")


# --------------------------------------------------- the shares add up

def test_the_eight_expert_shares_and_the_shared_experts_add_up(params):
    """One chip's share under expert parallelism, tied to the model: the
    routed parts that the shares compute (ops/moe.py told which experts it
    holds: here eight shares of ONE expert each, the router over all 8)
    plus the shared experts, which every chip computes alike, counted
    ONCE, add up to the uncut layer's FFN, program and reference alike."""
    from ray_tpu.models.decoder import ffn

    import flax.linen as nn

    class Ffn(nn.Module):
        cfg: Cohere2MoeConfig

        @nn.compact
        def __call__(self, y):
            return ffn(self.cfg, y, False, None, lambda down: down)

    layer = params["params"]["layer_1"]
    tree = {"params": {k: layer[k] for k in
                       ("moe", "shared_gate", "shared_up", "shared_down")}}
    y = jax.random.normal(jax.random.PRNGKey(3), (2, 11, 64), jnp.float32)
    whole = Ffn(CFG).apply(tree, y)
    h = y.reshape(22, 64)
    want = ref._experts_eager(h, layer["moe"], CONFIG) \
        + ref._shared(h, layer, CONFIG)
    np.testing.assert_allclose(whole.reshape(22, 64), want, atol=2e-5)
    shared = ref._shared(h, layer, CONFIG).reshape(2, 11, 64)
    routed = 0.0
    for first in range(8):
        cfg = dataclasses.replace(CFG, first_expert=first, held_experts=1)
        share = dict(tree["params"], moe={
            "router": layer["moe"]["router"], **{
                k: layer["moe"][k][first:first + 1]
                for k in ("w_gate", "w_up", "w_down")}})
        part = Ffn(cfg).apply({"params": share}, y)
        config = dict(CONFIG, num_experts=1, first_expert=first,
                      published={"num_experts": 8})
        np.testing.assert_allclose(
            part.reshape(22, 64),
            ref._experts_eager(h, share["moe"], config)
            + shared.reshape(22, 64), atol=2e-5)
        routed = routed + (part - shared)
    np.testing.assert_allclose(routed + shared, whole, atol=3e-5)


# --------------------------------- the cell's share, combined by blocks

def test_the_cells_share_combines_by_blocks_and_equals_the_reference():
    """The cell's experts at a reduced depth and width: 16 of 128 held,
    top-8, one window layer and the full one.  A prompt of 1,100 tokens
    prefills in the 2,048 bucket: 16,384 pairs in a capacity of 4,224,
    which ``ops/moe.py combine_blocks`` sends through 16 blocks of 128
    tokens.  Both layers take the compact branch, and the prefill and 6
    decode steps equal the reference's full forward as the tiny model's
    do."""
    from ray_tpu.ops.moe import (combine_blocks, compact_capacity,
                                 moe_layers)

    cfg = Cohere2MoeConfig.tiny(
        remat=False, layer_types=(SLIDING, FULL), n_experts=128,
        experts_per_token=8, first_expert=32, held_experts=16,
        max_seq=2048)
    config = dict(CONFIG, num_hidden_layers=2, num_experts=16,
                  first_expert=32, num_experts_per_tok=8,
                  layer_types=list(cfg.layer_types),
                  published={"num_experts": 128})
    capacity = compact_capacity(2048 * 8, 16, 128)
    assert (capacity, combine_blocks(2048, 8, capacity)) == (4224, 16)
    params = _scaled(cohere2_moe_init(cfg, jax.random.PRNGKey(11)))
    prompt = np.random.default_rng(5).integers(0, 256, 1100).tolist()
    padded = jnp.asarray([prompt + [0] * (2048 - 1100)], jnp.int32)
    _, state = Cohere2Moe(cfg).apply(params, padded,
                                     mutable=["intermediates"])
    layers = moe_layers(state["intermediates"])
    assert [bool(m["compact"]) for m in layers] == [True, True]
    assert all(1100 < int(m["load"].sum()) <= capacity for m in layers)
    served, logits = faults.serve(cfg, params, [prompt], 7, max_batch=2)
    want = np.asarray(ref.forward(
        config, params, jnp.asarray([prompt + served[0][:-1]], jnp.int32))
    )[0][len(prompt) - 1:]
    assert float(np.std(want)) > 0.3
    np.testing.assert_allclose(np.stack(logits[0]), want, atol=1e-4)


# ---------------------------------------------------- names and the registry

def _lowered(cfg, shape):
    from ray_tpu.llm.engine import jit_forward
    from ray_tpu.llm.kv_cache import init_pool, pages_for

    spec = MODEL_FAMILIES["cohere2moe"].cache(cfg)
    params = jax.eval_shape(
        lambda: cohere2_moe_init(cfg, jax.random.PRNGKey(0)))
    kv = jax.eval_shape(lambda: init_pool(spec, 16, 4, cfg.dtype, 4))
    ints = jax.ShapeDtypeStruct(shape, jnp.int32)
    return jit_forward(Cohere2Moe(cfg)).trace(
        params, ints, *kv.values(),
        jax.ShapeDtypeStruct((shape[0], pages_for(cfg.max_seq, 4)),
                             jnp.int32),
        jax.ShapeDtypeStruct((shape[0], 2), jnp.int32),
        ints).lower().as_text(debug_info=True)


def test_the_lowered_forward_names_the_scopes_the_readers_file_by():
    """benchmark/harness/swa_phases.py files a trace's operations by these
    names: a window layer's cached core under ``attn.core/attn.window``, the
    full layer's under ``attn.core/attn.full``, ``kv.store`` inside both; a
    decode step's ``kv.attend`` inside them too, a prefill has none (it
    attends among its own rows); the shared experts under ``moe.shared``;
    a rotation in the window layers alone."""
    decode, prefill = _lowered(CFG, (2, 1)), _lowered(CFG, (1, 16))
    both = ("attn.qkv", "attn.core/attn.window/kv.store",
            "attn.core/attn.full/kv.store", "attn.out", "moe/moe.route",
            "mlp/moe.shared", "lm_head")
    for name in both + ("attn.core/attn.window/kv.attend",
                        "attn.core/attn.full/kv.attend"):
        assert name in decode, name
    for name in both:
        assert name in prefill, name
    assert "kv.attend" not in prefill
    for text in (decode, prefill):      # q and k of three layers, not four
        assert text.count("stablehlo.sine ") == 6
    assert "mlp_norm" not in decode and "layer_0/norm" in decode


def test_the_registry_builds_the_tenth_family():
    row = MODEL_FAMILIES["cohere2moe"]
    assert len(MODEL_FAMILIES) == 10 and row.config is Cohere2MoeConfig
    assert family_of(CFG) is row and row.module is Cohere2Moe
    assert row.cache(CFG) == CacheSpec(
        kv_layers=1, kv_heads=2, head_dim=32, window_layers=3, window=8)
    published = Cohere2MoeConfig()
    assert published.layer_types == (SLIDING, SLIDING, SLIDING, FULL) * 8
    assert row.cache(published) == CacheSpec(
        kv_layers=8, kv_heads=8, head_dim=128, window_layers=24,
        window=4096)
    assert (published.shared_d_ff, published.shared_multiplier) == (
        16384, 0.25)
    # one layer outside its routed experts, and one routed expert
    shapes = jax.eval_shape(lambda: cohere2_moe_init(
        dataclasses.replace(published, layer_types=(SLIDING,),
                            held_experts=1, vocab_size=8),
        jax.random.PRNGKey(0)))["params"]["layer_0"]
    sizes = {k: sum(a.size for a in jax.tree_util.tree_leaves(v))
             for k, v in shapes.items()}
    assert sizes.pop("moe") - 4096 * 128 == 3 * 4096 * 4096 == 50_331_648
    assert sum(sizes.values()) + 4096 * 128 == 344_461_312  # 344.5M
