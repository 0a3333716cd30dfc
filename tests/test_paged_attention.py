"""The paged-decode kernel (ops/paged_attention.py) in interpret mode
against ``llm/kv_cache.py paged_attend``, its plain definition, and
against float32 softmax attention over the pool's own values.

One batch a head layout holds every length that matters: 0 (a padded
row: zeros), 1, exactly a page, exactly a block, one past a block, the
whole context, and two ragged ones.  The physical pages are scrambled;
every page no live sequence owns is NaN in K and V, and so is every K
slot beyond a sequence's length inside its last page: a kernel that
read or leaked anything beyond ``lengths`` would return NaN.

Tolerance: the pool is bf16 and so is the output; kernel and definition
both round the probabilities to bf16 before the product with V (the one
before, the other after the softmax's division), so they agree to a few
bf16 steps of an output of size ~1: 0.02 absolute, against the float32
reference too.  A float32 pool is held to 1e-5.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

PAGE, PAGES_PER_SEQ = 16, 16          # max_context 256 = two blocks of 8
LENGTHS = {"padded_row": 0, "one": 1, "a_page": 16, "a_block": 128,
           "one_past_a_block": 129, "max_context": 256, "ragged_37": 37,
           "ragged_200": 200}
LAYOUTS = {                    # h, h_kv, d, pool dtype, block_pages
    "mha_5x64": (5, 5, 64, jnp.bfloat16, 8),
    "mha_2x128": (2, 2, 128, jnp.bfloat16, 8),
    "gqa_4_over_2": (4, 2, 64, jnp.bfloat16, 8),
    "f32_blocks_of_2_pages": (3, 3, 32, jnp.float32, 2),
}


def _softmax_attention(q, k, v, d):
    """float32, one sequence: q [h, d], k/v [n, h, d] -> [h, d]."""
    s = np.einsum("hd,nhd->hn", q, k) * d ** -0.5
    p = np.exp(s - s.max(axis=1, keepdims=True))
    return np.einsum("hn,nhd->hd", p / p.sum(axis=1, keepdims=True), v)


@functools.lru_cache(maxsize=None)
def _run(layout):
    """(kernel out, paged_attend out, float32 reference) as float32
    [B, h, d], for the batch of LENGTHS at ``layout``."""
    from jax.experimental.pallas import tpu as pltpu

    from ray_tpu.llm.kv_cache import paged_attend
    from ray_tpu.ops.paged_attention import paged_decode

    h, h_kv, d, dtype, block_pages = LAYOUTS[layout]
    lengths = list(LENGTHS.values())
    n_seq, layers, layer = len(lengths), 3, 1
    num_pages = n_seq * PAGES_PER_SEQ + 5
    rng = np.random.default_rng(29)
    shape = (layers, num_pages, PAGE, h_kv * d)
    k = rng.standard_normal(shape).astype(np.float32)
    v = rng.standard_normal(shape).astype(np.float32)
    q = rng.standard_normal((n_seq, 1, h, d)).astype(np.float32)
    # Scrambled physical pages; page 0, which every unused table entry
    # names, belongs to nobody.
    perm = rng.permutation(np.arange(1, num_pages))
    table = np.zeros((n_seq, PAGES_PER_SEQ), np.int32)
    live = np.zeros((num_pages, PAGE), bool)
    taken = 0
    for b, n in enumerate(lengths):
        own = perm[taken:taken + -(-n // PAGE)]
        taken += len(own)
        table[b, :len(own)] = own
        live[own] = True
        if n % PAGE:
            live[own[-1], n % PAGE:] = False
            k[:, own[-1], n % PAGE:] = np.nan       # K only: see above
    k[:, ~live.any(axis=1)] = np.nan
    v[:, ~live.any(axis=1)] = np.nan
    kj, vj, qj = (jnp.asarray(x, dtype) for x in (k, v, q))
    lens = jnp.asarray(lengths, jnp.int32)
    out = paged_decode(
        qj, kj, vj, layer, jnp.asarray(table), lens,
        block_pages=block_pages,
        # The TPU interpreter: memory no copy has written reads as NaN.
        interpret=pltpu.InterpretParams())
    # The definition multiplies 0 by what lies beyond: give it numbers.
    plain = paged_attend(
        qj, jnp.nan_to_num(kj), jnp.nan_to_num(vj), layer,
        jnp.asarray(table), (lens - 1)[:, None])
    k32, v32, q32 = (np.asarray(x.astype(jnp.float32))
                     for x in (kj, vj, qj))
    ref = np.zeros((n_seq, h, d), np.float32)
    for b, n in enumerate(lengths):
        if n:
            pages = table[b, :-(-n // PAGE)]
            rows = [x[layer, pages].reshape(-1, h_kv, d)[:n]
                    .repeat(h // h_kv, axis=1) for x in (k32, v32)]
            ref[b] = _softmax_attention(q32[b, 0], *rows, d)
    assert out.shape == qj.shape and out.dtype == qj.dtype
    return (np.asarray(out.astype(jnp.float32))[:, 0],
            np.asarray(plain.astype(jnp.float32))[:, 0], ref)


@pytest.mark.parametrize("row", list(LENGTHS))
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_paged_decode_matches_paged_attend(layout, row):
    out, plain, ref = (x[list(LENGTHS).index(row)] for x in _run(layout))
    assert np.isfinite(out).all()
    if LENGTHS[row] == 0:
        assert not out.any()        # zeros, whatever page 0 holds
        return
    tol = 0.02 if LAYOUTS[layout][3] == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(out, plain, atol=tol, rtol=tol)
    np.testing.assert_allclose(out, ref, atol=tol, rtol=tol)


def test_supported_takes_whole_tiles_of_a_decode_step():
    """The dispatch's shape test: one query row a sequence, folded rows
    of whole 128-lane tiles, pages of whole sublane tiles."""
    from ray_tpu.ops.paged_attention import supported

    def shapes(t, page, width, dtype=jnp.bfloat16):
        return (jax.ShapeDtypeStruct((16, t, 20, 64), dtype),
                jax.ShapeDtypeStruct((36, 1024, page, width), dtype))

    assert supported(*shapes(1, 16, 1280))
    assert supported(*shapes(1, 8, 1280, jnp.float32))
    assert not supported(*shapes(512, 16, 1280))        # a prefill
    assert not supported(*shapes(1, 16, 64))            # a tiny model
    assert not supported(*shapes(1, 8, 1280))           # half a bf16 tile


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_engine_tokens_are_the_same_through_the_kernel(family,
                                                       monkeypatch):
    """A tiny model's greedy tokens through GenerationEngine with every
    decode step's attention forced through the kernel (interpreted, as
    off the TPU) equal the ``paged_attend`` path's, over requests that
    share decode steps at ragged lengths; and the engine counts what
    that attention read.  The tiny Llama has grouped-query heads."""
    import ray_tpu.models.attention as attention
    from ray_tpu.llm.engine import EngineConfig, GenerationEngine
    from ray_tpu.models import MODEL_FAMILIES
    from ray_tpu.ops import paged_attention

    fam = MODEL_FAMILIES[family]
    cfg = dataclasses.replace(fam.tiny(), remat=False, dtype=jnp.float32)
    assert (fam.cache(cfg).kv_heads < cfg.n_head) == (family == "llama")
    # Weights large enough that the greedy tokens vary.
    params = jax.tree_util.tree_map(
        lambda x: x * 6.0, fam.init(cfg, jax.random.PRNGKey(5)))
    prompts = [[3, 17, 42, 7, 99, 5, 23, 11, 2, 64, 31, 8, 90, 12, 55,
                71, 6, 19], [9, 4], [80, 1, 33, 27, 60]]
    calls = []
    real = paged_attention.paged_decode

    def spy(*args, **kwargs):
        calls.append(args[0].shape)
        return real(*args, **kwargs)

    def generate(kernel):
        if kernel:
            monkeypatch.setattr(attention, "_decode_kernel",
                                lambda q, k_pages: q.shape[1] == 1)
            monkeypatch.setattr(paged_attention, "paged_decode", spy)
        engine = GenerationEngine(
            model=family, model_cfg=cfg, params=params,
            engine_cfg=EngineConfig(max_batch=4, num_pages=32))
        seqs = [engine.submit(p, max_tokens=n)
                for p, n in zip(prompts, (9, 14, 6))]
        while any(not s.finished for s in seqs):
            engine.step()
        return ([s.tokens[len(p):] for s, p in zip(seqs, prompts)],
                engine.stats())

    plain, _ = generate(False)
    assert not calls
    through_kernel, stats = generate(True)
    assert calls and set(calls) == {(4, 1, cfg.n_head,
                                     cfg.d_model // cfg.n_head)}
    assert through_kernel == plain
    assert len({t for out in plain for t in out}) > 4
    counts = stats["attention"]
    assert counts["decode_runs"] == stats["steps"] > 0
    assert 0 < counts["kv_rows_read"] < counts["kv_rows_held"]
    assert counts["kv_rows_read"] % (16 * cfg.n_layer) == 0


# ------------------------------- a K/V prefill attends among its own rows

def _parent_form(cfg, q, k, v, cache=None, scale=None):
    """The cached branch as it was before PR 48: store, then
    ``paged_attend`` over the sequence's whole page table."""
    from ray_tpu.llm.kv_cache import paged_attend, paged_store

    k_pages, v_pages = paged_store(
        cache["k_pages"], cache["v_pages"], cache["layer"], k, v,
        cache["page_table"], cache["positions"])
    return paged_attend(q, k_pages, v_pages, cache["layer"],
                        cache["page_table"], cache["positions"],
                        scale=scale), (k_pages, v_pages)


@pytest.mark.parametrize("n", [16, 11, 3], ids=["whole", "padded", "short"])
@pytest.mark.parametrize("family", ["gpt2", "olmoe", "granitemoehybrid",
                                    "lfm2moe"])
def test_a_prefill_among_its_own_rows_equals_paged_attend_over_the_pool(
        family, n, monkeypatch):
    """The engine's prefill ([1, 16], ``n`` real positions from 0, the rest
    padding behind them) of the four K/V families' tiny presets (GPT-2's
    learned positions, OLMoE's QK-norm over the width and RoPE, Granite's
    grouped heads with no rotation and its own scale beside the state
    pool, LFM2's per-head QK-norm) through ``models/attention.py
    attention`` as it is, which attends among the step's own rows, against
    the same forward with the cached branch as it was (``paged_attend``
    over the page table, gathered from a pool that held other numbers):
    the real positions' logits agree to float32's rounding, the pool's
    first layer and every page the prompt does not own are BIT-equal, the
    other layers' rows (which follow the attention before them) to 2e-5."""
    import ray_tpu.models.decoder as decoder
    import ray_tpu.models.gpt2 as gpt2
    from ray_tpu.llm.engine import jit_forward
    from ray_tpu.llm.kv_cache import (init_pool, init_state, pool_arrays,
                                      state_arrays)
    from ray_tpu.models import MODEL_FAMILIES

    fam = MODEL_FAMILIES[family]
    cfg = dataclasses.replace(fam.tiny(), remat=False, dtype=jnp.float32)
    params = fam.init(cfg, jax.random.PRNGKey(2))
    params = jax.tree_util.tree_map(
        lambda w: 8.0 * w if w.ndim > 1 else w, params)
    spec = fam.cache(cfg)
    rng = np.random.default_rng(n)
    tokens = np.zeros((1, 16), np.int32)
    tokens[0, :n] = rng.integers(0, cfg.vocab_size, n)
    positions = np.full((1, 16), -1, np.int32)
    positions[0, :n] = np.arange(n)
    table = np.array([[5, 2, 7, 1, 0, 0, 0, 0]], np.int32)  # pages of 4

    def run():
        pools = [a + 1 for a in init_pool(spec, 9, 4, jnp.float32).values()]
        state = [a + 1 for a in init_state(spec, 2, jnp.float32).values()]
        slots = [np.array([1], np.int32)] if state else []
        assert len(pools) == len(pool_arrays(spec)) == 2
        assert len(state) == len(state_arrays(spec))
        out = jit_forward(fam.module(cfg))(
            params, tokens, *pools, table, positions, *state, *slots)
        return (np.asarray(out[0]),) + tuple(np.asarray(a) for a in out[1:3])

    logits, k_own, v_own = run()
    monkeypatch.setattr(decoder, "attention", _parent_form)
    monkeypatch.setattr(gpt2, "attention", _parent_form)
    want, k_pool, v_pool = run()
    size = float(np.max(np.abs(want[0, :n])))
    assert size > 1e-3 and float(np.max(np.abs(
        logits[0, :n] - want[0, :n]))) < 1e-4 * size
    owned = sorted(set(table[0, :-(-n // 4)].tolist()))
    others = [p for p in range(9) if p not in owned]
    for own, pool in ((k_own, k_pool), (v_own, v_pool)):
        np.testing.assert_array_equal(own[0], pool[0])
        np.testing.assert_array_equal(own[:, others], pool[:, others])
        np.testing.assert_array_equal(own[:, others], 1.0)
        np.testing.assert_allclose(own, pool, atol=2e-5, rtol=1e-4)
