"""LLM inference plane units (no cluster): sampling vs numpy
references, the paged KV page pool, decode-mode forwards token-
identical to the full-sequence forward for GPT-2 and Llama, RoPE table
caching, decode FLOPs helpers, and the telemetry surfacing."""

import dataclasses

import jax
import numpy as np
import pytest

from ray_tpu.llm.sampling import (SamplingParams, apply_temperature,
                                  greedy, jit_sampler, keep_mask,
                                  pack_rows, sample, seed_words, softmax,
                                  top_k_mask, top_p_mask)

# ------------------------------------------------------------ sampling


def test_greedy_is_argmax():
    logits = np.array([0.1, 3.0, -2.0, 2.9])
    assert greedy(logits) == 1
    assert sample(logits, SamplingParams(temperature=0.0)) == 1
    # temperature 0 wins over any filter settings
    assert sample(logits, SamplingParams(temperature=0.0, top_k=3,
                                         top_p=0.5)) == 1


def test_temperature_scales_logits():
    logits = np.array([1.0, 2.0, 4.0])
    np.testing.assert_allclose(apply_temperature(logits, 2.0),
                               [0.5, 1.0, 2.0])
    # High temperature flattens the distribution toward uniform.
    hot = softmax(apply_temperature(logits, 100.0))
    assert np.max(hot) - np.min(hot) < 0.02


def test_top_k_mask_reference():
    logits = np.array([0.5, 2.0, 1.5, -1.0, 3.0])
    out = top_k_mask(logits, 2)
    keep = {int(i) for i in np.argsort(-logits)[:2]}
    for i in range(5):
        if i in keep:
            assert out[i] == logits[i]
        else:
            assert out[i] == -np.inf
    # k=0 and k>=V are no-ops.
    np.testing.assert_array_equal(top_k_mask(logits, 0), logits)
    np.testing.assert_array_equal(top_k_mask(logits, 5), logits)


def test_top_p_mask_reference():
    probs = np.array([0.5, 0.3, 0.15, 0.05])
    logits = np.log(probs)
    out = top_p_mask(logits, 0.7)
    # Mass before token 2 is 0.8 >= 0.7: tokens {0, 1} survive (the
    # token crossing the threshold is included).
    assert np.isfinite(out[0]) and np.isfinite(out[1])
    assert out[2] == -np.inf and out[3] == -np.inf
    # p tiny: only the top token survives -> sampling is greedy.
    rng = np.random.default_rng(0)
    for _ in range(20):
        assert sample(logits, SamplingParams(temperature=1.0,
                                             top_p=1e-9), rng) == 0
    # p=1.0 is a no-op.
    np.testing.assert_array_equal(top_p_mask(logits, 1.0), logits)


def test_sample_respects_top_k_support():
    logits = np.array([5.0, 4.9, -100.0, -100.0, 4.8])
    rng = np.random.default_rng(1)
    drawn = {sample(logits, SamplingParams(temperature=1.0, top_k=2),
                    rng) for _ in range(200)}
    assert drawn <= {0, 1}
    assert len(drawn) == 2   # genuinely stochastic within the support


def test_sample_matches_numpy_reference_distribution():
    logits = np.array([1.0, 0.5, 0.0, -0.5])
    ref = softmax(apply_temperature(logits, 0.7))
    rng = np.random.default_rng(7)
    n = 4000
    counts = np.bincount(
        [sample(logits, SamplingParams(temperature=0.7), rng)
         for _ in range(n)], minlength=4)
    np.testing.assert_allclose(counts / n, ref, atol=0.03)


def test_sampling_params_validate():
    with pytest.raises(ValueError):
        SamplingParams(temperature=-1.0).validate()
    with pytest.raises(ValueError):
        SamplingParams(top_k=-2).validate()
    with pytest.raises(ValueError):
        SamplingParams(top_p=0.0).validate()
    with pytest.raises(ValueError):
        SamplingParams(top_p=1.5).validate()
    SamplingParams(temperature=0.8, top_k=40, top_p=0.95).validate()


# ------------------------------------------ the sampler on the device

GPT2_VOCAB = 50257


def _device_tokens(rows, params, seeds=None, indices=None, pad=0):
    """``sample_tokens`` over ``rows`` [B, V] (``pad`` padded rows of
    NaN behind them), one SamplingParams a row."""
    rows = np.asarray(rows, np.float32)
    n, vocab = rows.shape
    seeds = seeds if seeds is not None else range(n)
    indices = indices if indices is not None else [0] * n
    logits = np.full((n + pad, 1, vocab), np.nan, np.float32)
    logits[:n, 0] = rows
    knobs, words = pack_rows(
        zip(params, map(seed_words, seeds), indices), n + pad)
    return np.asarray(jit_sampler(n + pad)[0](logits, knobs, words))[:n]


def _seeded_rows(seed, n=3, vocab=GPT2_VOCAB, scale=3.0):
    return (np.random.default_rng(seed).standard_normal((n, vocab))
            * scale).astype(np.float32)


def _reference_keep(row, top_k, top_p):
    return np.isfinite(top_p_mask(top_k_mask(
        np.asarray(row, np.float64), top_k), top_p))


@pytest.mark.parametrize("case", ["distinct", "ties", "all_equal",
                                  "filters_ignored"])
def test_device_greedy_is_numpy_argmax(case):
    rows = _seeded_rows(11)
    params = [SamplingParams()] * 3
    if case == "ties":      # the largest value twice: the lower index
        for r, (i, j) in zip(rows, [(40000, 7), (123, 50256), (9, 10)]):
            r[i] = r[j] = r.max() + 1.0
    elif case == "all_equal":
        rows[:] = 0.25
    elif case == "filters_ignored":
        params = [SamplingParams(0.0, top_k=3, top_p=0.5)] * 3
    got = _device_tokens(rows, params)
    np.testing.assert_array_equal(got, np.argmax(rows, axis=-1))
    assert got.dtype == np.int32


@pytest.mark.parametrize("top_k,top_p", [
    (50, 1.0), (1, 1.0), (0, 1.0), (GPT2_VOCAB, 1.0), (GPT2_VOCAB + 5, 1.0),
    (0, 0.95), (0, 0.5), (0, 1e-9), (40, 0.9), (1000, 0.3), (5, 0.999)])
def test_device_keep_mask_is_the_reference_set(top_k, top_p):
    """The searched cut-offs leave what the reference's sort leaves, on
    seeded rows the size of GPT-2's vocabulary at three temperatures.
    float32 sums against the reference's float64: a token whose mass
    before it lies within 1e-5 of top_p may fall either way."""
    rows = _seeded_rows(top_k * 7 + int(top_p * 1000)) \
        / np.array([[0.7], [1.0], [1.3]], np.float32)
    got = np.asarray(jax.jit(keep_mask)(
        rows, np.full(3, top_k, np.int32), np.full(3, top_p, np.float32)))
    for row, mask in zip(rows, got):
        want = _reference_keep(row, top_k, top_p)
        firm = _reference_keep(row, top_k, max(top_p - 1e-5, 1e-12)) \
            if top_p < 1.0 else want
        loose = _reference_keep(row, top_k, min(top_p + 1e-5, 1.0)) \
            if top_p < 1.0 else want
        assert mask.sum() >= 1
        assert not (firm & ~mask).any() and not (mask & ~loose).any()
        assert abs(int(mask.sum()) - int(want.sum())) <= 1


@pytest.mark.parametrize("case", ["ties_at_kth", "ties_at_kth_then_top_p",
                                  "negative_and_zero", "top_p_tiny_tied"])
def test_device_keep_mask_with_ties(case):
    row = _seeded_rows(5, n=1)[0]
    top = np.argsort(-row)
    if case == "ties_at_kth":       # the 3rd, 4th and 5th largest equal
        row[top[2:5]] = row[top[2]]
        got = np.asarray(jax.jit(keep_mask)(
            row[None], np.array([3], np.int32), np.ones(1, np.float32)))[0]
        want = _reference_keep(row, 3, 1.0)
        assert want.sum() == 5      # ties stay, as top_k_mask
    elif case == "ties_at_kth_then_top_p":
        row[top[2:5]] = row[top[2]]
        row[top[:2]] += 4.0         # the nucleus ends inside the top two
        got = np.asarray(jax.jit(keep_mask)(
            row[None], np.array([3], np.int32),
            np.array([0.6], np.float32)))[0]
        want = _reference_keep(row, 3, 0.6)
    elif case == "negative_and_zero":   # -0.0 and 0.0 are one value
        row = np.array([-1.0, -0.0, 0.0, -2.0, -3.0], np.float32)
        got = np.asarray(jax.jit(keep_mask)(
            row[None], np.array([1], np.int32), np.ones(1, np.float32)))[0]
        want = _reference_keep(row, 1, 1.0)
        assert want.sum() == 2
    else:   # the largest value twice under a tiny top_p: the reference
        # keeps the lower index, the search keeps both (the documented
        # difference: ties on the nucleus's boundary all stay)
        row[top[1]] = row[top[0]]
        got = np.asarray(jax.jit(keep_mask)(
            row[None], np.zeros(1, np.int32),
            np.array([1e-9], np.float32)))[0]
        want = np.zeros_like(got)
        want[top[:2]] = True
        assert _reference_keep(row, 0, 1e-9).sum() == 1
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("params", [
    SamplingParams(temperature=0.7),
    SamplingParams(temperature=1.0, top_k=3),
    SamplingParams(temperature=1.3, top_p=0.8),
    SamplingParams(temperature=0.9, top_k=6, top_p=0.9)])
def test_device_sample_matches_numpy_reference_distribution(params):
    """20k draws of one row (one seed, the token's index counting up)
    against the reference's probabilities."""
    logits = np.array([1.0, 0.5, 0.0, -0.5, 2.0, -1.5, 0.25, 1.5],
                      np.float32)
    x = top_p_mask(top_k_mask(apply_temperature(logits, params.temperature),
                              params.top_k), params.top_p)
    ref = softmax(x)
    n = 20000
    got = _device_tokens(np.tile(logits, (n, 1)), [params] * n,
                         seeds=[1234567] * n, indices=range(n))
    counts = np.bincount(got, minlength=len(logits))
    assert set(np.flatnonzero(counts)) <= set(np.flatnonzero(ref))
    np.testing.assert_allclose(counts / n, ref, atol=0.012)


@pytest.mark.parametrize("neighbours", ["padding", "nan", "other_requests",
                                        "other_slot"])
def test_device_rows_do_not_disturb_each_other(neighbours):
    """A request's token depends on its logits, its parameters, its seed
    and the token's index: not on its slot, on padding or on NaN rows."""
    rows = _seeded_rows(21, n=2)
    params = [SamplingParams(0.8, top_p=0.95), SamplingParams()]
    alone = _device_tokens(rows, params, seeds=[77, 5], indices=[3, 9])
    if neighbours == "padding":
        got = _device_tokens(rows, params, seeds=[77, 5], indices=[3, 9],
                             pad=6)
    elif neighbours == "nan":
        wide = np.concatenate([rows, np.full_like(rows, np.nan)])
        got = _device_tokens(
            wide, params + [SamplingParams(1.0, top_k=4, top_p=0.5)] * 2,
            seeds=[77, 5, 1, 2], indices=[3, 9, 0, 0])[:2]
    elif neighbours == "other_requests":
        wide = np.concatenate([rows, _seeded_rows(22, n=2)])
        got = _device_tokens(
            wide, params + [SamplingParams(1.2, top_k=50)] * 2,
            seeds=[77, 5, 1 << 40, -3], indices=[3, 9, 1, 2])[:2]
    else:
        got = _device_tokens(rows[::-1], params[::-1], seeds=[5, 77],
                             indices=[9, 3])[::-1]
    np.testing.assert_array_equal(got, alone)
    assert alone[1] == np.argmax(rows[1])


def test_device_draws_follow_seed_and_index():
    rows = np.tile(_seeded_rows(31, n=1, vocab=4096, scale=1.0), (6, 1))
    p = [SamplingParams(temperature=1.0)] * 6
    big = (1 << 70) + 12345         # any Python int is a seed
    got = _device_tokens(rows, p, seeds=[9, 9, 10, big, big, -1],
                         indices=[0, 1, 0, 5, 5, 0])
    assert got[3] == got[4]
    assert len(set(got.tolist())) >= 4    # 4096 tokens, near-flat row
    assert seed_words(big) == (12345, 0) and seed_words(-1) == (
        0xFFFFFFFF, 0xFFFFFFFF)


def test_last_rows_places_the_prefill_row():
    """What a prefill's forward returns, ONE position's logits
    [1, 1, V], goes into row 0 of the sampler's shape."""
    _, last_rows = jit_sampler(4)
    logits = np.arange(5, dtype=np.float32).reshape(1, 1, 5) - 2
    out = np.asarray(last_rows(logits))
    assert out.shape == (4, 1, 5)
    np.testing.assert_array_equal(out[0, 0], logits[0, 0])
    assert not out[1:].any()


# ------------------------------------------------------------ page pool


def _gauge_value(name: str) -> float:
    from ray_tpu.util.metrics import registry

    for snap in registry().snapshot():
        if snap["name"] == name:
            return snap["series"][0]["value"]
    raise AssertionError(f"gauge {name} not published")


def test_page_pool_accounting_and_gauges():
    from ray_tpu.llm.kv_cache import PagePool

    pool = PagePool(8, 16)
    assert pool.available == 8 and pool.used == 0
    assert _gauge_value("rt_llm_kv_pages_total") == 8.0
    a = pool.alloc(3)
    assert len(a) == 3 and pool.used == 3
    assert _gauge_value("rt_llm_kv_pages_used") == 3.0
    # All-or-nothing: 6 > 5 available -> None, nothing consumed.
    assert pool.alloc(6) is None
    assert pool.used == 3
    b = pool.alloc(5)
    assert pool.used == 8 and pool.alloc(1) is None
    pool.free(a)
    pool.free(b)
    assert pool.used == 0
    assert _gauge_value("rt_llm_kv_pages_used") == 0.0
    # Distinct pages throughout.
    assert len(set(a) | set(b)) == 8
    with pytest.raises(AssertionError):
        pool.free([0])   # over-free is a bug, loudly


def test_pages_for():
    from ray_tpu.llm.kv_cache import pages_for

    assert pages_for(1, 16) == 1
    assert pages_for(16, 16) == 1
    assert pages_for(17, 16) == 2
    assert pages_for(0, 16) == 1   # a sequence always holds >=1 page


# ------------------------ the one object over what a sequence holds

# One family a layout of the device's caches: K/V; K/V + conv + ssm;
# K/V + conv; latent; latent + a state; K/V in two groups.
LAYOUTS = {"kv": "gpt2", "kv_conv_ssm": "granitemoehybrid",
           "kv_conv": "lfm2moe", "latent": "kimik2",
           "latent_state": "kimilinear", "kv_two_groups": "cohere2moe"}


def _sequence_cache(layout, num_pages=10, max_batch=2):
    """A cache of pages of 4 over ``layout``'s tiny spec (a context of
    ``num_pages`` pages), and the spec."""
    from ray_tpu.llm.kv_cache import SequenceCache
    from ray_tpu.models import MODEL_FAMILIES

    fam = MODEL_FAMILIES[LAYOUTS[layout]]
    cfg = fam.tiny()
    spec = fam.cache(cfg)
    return SequenceCache(spec, num_pages=num_pages, page_size=4,
                         max_batch=max_batch, max_context=None,
                         max_seq=cfg.max_seq, dtype=cfg.dtype,
                         mixer_weight_bytes=7), spec


def _in_use(cache):
    return [pool.used for pool in cache.pools.values()] + [cache.slots.used]


@pytest.mark.parametrize("layout", LAYOUTS)
def test_sequence_cache_takes_everything_or_nothing(layout):
    """Pages, then the ring, then a slot: a refusal at any of the three
    leaves nothing taken; "larger than the pool" is told from "not now";
    a page more only where a position opens one; everything goes back
    once, and a second give-back gives nothing."""
    from ray_tpu.llm.kv_cache import Holding

    cache, spec = _sequence_cache(layout)
    ring = 2 if spec.window_layers else 0
    assert (cache.max_context, cache.pages_per_seq) == (40, 10)
    assert cache.ring_pages == ring
    assert list(cache.pools) == ["full"] + ["window"] * bool(ring)
    nothing = [0] * len(cache.pools) + [0]
    a, b, c = Holding(), Holding(), Holding()
    # larger than the whole pool: never; the whole pool: now
    assert not cache.fits(41) and cache.fits(40)
    assert cache.take(a, 23)                    # 6 pages of 10
    assert (len(a.pages), len(a.ring), a.slot) == (6, ring, 0)
    # pages refused: it fits, but not now
    assert cache.fits(20) and not cache.take(b, 20)
    assert (b.pages, b.ring, b.slot) == ([], [], None)
    assert _in_use(cache) == [6] + [ring] * bool(ring) + [1]
    if ring:
        # pages taken, ring refused: the pages go back
        rest = cache.pools["window"].alloc(2)
        assert not cache.take(b, 8) and _in_use(cache) == [6, 4, 1]
        cache.pools["window"].free(rest)
    # pages (and ring) taken, slot refused: both go back
    spare = cache.slots.take()
    assert not cache.take(b, 8)
    assert (b.pages, b.ring, b.slot) == ([], [], None)
    assert _in_use(cache) == [6] + [ring] * bool(ring) + [2]
    cache.slots.give(spare)
    assert cache.take(b, 8) and b.slot == 1     # 2 pages more, the last slot
    assert not cache.take(c, 4)                 # as many rings, slots as rows
    assert _in_use(cache) == [8] + [4] * bool(ring) + [2]
    # room for a position: within its pages nothing, past them one page
    assert cache.grow(b, 7) and len(b.pages) == 2
    assert cache.grow(b, 8) and len(b.pages) == 3
    assert cache.grow(b, 12) and len(b.pages) == 4
    assert not cache.grow(b, 16) and len(b.pages) == 4      # dry
    assert len(b.ring) == ring                  # whole from the start
    for held in (a, b, a, b, c):                # the second time: nothing
        cache.release(held)
        assert (held.pages, held.ring, held.slot) == ([], [], None)
    assert _in_use(cache) == nothing
    assert cache.take(c, 40) and c.slot == 0    # lowest slot first


class _Owner:
    def __init__(self, held):
        self.held = held


@pytest.mark.parametrize("layout", LAYOUTS)
def test_sequence_cache_hands_the_forward_its_arguments(layout):
    """The arrays a spec names, in the forward's order; the tables of a
    launch, one a group, zeros for the rows without a sequence; the
    donated arrays taken back from a forward's outputs, the rest
    returned; its stats() keys by layout."""
    from ray_tpu.llm.kv_cache import (Holding, pool_arrays, pool_tables,
                                      state_arrays)

    cache, spec = _sequence_cache(layout, max_batch=3)
    assert tuple(cache.paged) == pool_arrays(spec)
    assert tuple(cache.state) == state_arrays(spec)
    assert all(a.shape[:2] == (spec.state_layers, 3)
               for a in cache.state.values())
    groups = {"k_pages": (spec.kv_layers, 10), "v_pages": (spec.kv_layers,
                                                           10),
              "latent_pages": (spec.kv_layers, 10),
              "window_k_pages": (spec.window_layers, 3 * cache.ring_pages),
              "window_v_pages": (spec.window_layers, 3 * cache.ring_pages)}
    for name, a in cache.paged.items():
        assert a.shape[:3] == groups[name] + (4,)
    owners = [_Owner(Holding()), _Owner(Holding())]
    assert cache.take(owners[0].held, 9) and cache.take(owners[1].held, 2)
    tables = cache.tables([(owners[0], 2), (owners[1], 0)], 3)
    assert len(tables) == len(pool_tables(spec))
    table = tables[0]
    assert table.shape == (3, cache.pages_per_seq) and table.dtype == np.int32
    assert table[2, :3].tolist() == owners[0].held.pages
    assert table[0, :1].tolist() == owners[1].held.pages
    assert not table[1].any() and not table[2, 3:].any()
    if spec.window_layers:
        rings = tables[1]
        assert rings.shape == (3, cache.ring_pages)
        assert rings[2].tolist() == owners[0].held.ring
        assert not rings[1].any()
    one, = cache.tables([(owners[0], 0)], 1)[:1]
    assert one.shape == (1, cache.pages_per_seq)
    args = cache.args(tables, "positions", "slots")
    paged, state = list(cache.paged.values()), list(cache.state.values())
    want = paged + list(tables) + ["positions"] \
        + (state + ["slots"] if state else [])
    assert len(args) == len(want)
    assert all(x is y for x, y in zip(args, want))
    # outputs after the logits: the donated arrays, then what is left
    new = [object() for _ in paged + state]
    assert cache.take_back(new + ["moe", "residual"]) == ["moe", "residual"]
    assert list(cache.paged.values()) + list(cache.state.values()) == new
    assert tuple(cache.paged) == pool_arrays(spec)
    assert cache.take_back(new) == []
    stats = cache.stats()
    assert set(stats) == {"kv_pages_used", "kv_pages_total", "attention"} \
        | ({"kv_pages"} if spec.window_layers else set()) \
        | ({"state"} if spec.state_layers else set())
    assert (stats["kv_pages_used"], stats["kv_pages_total"]) == (4, 10)
    if spec.state_layers:
        assert stats["state"]["slots_used"] == 2
        assert stats["state"]["mixer_weight_bytes"] == 7


@pytest.mark.parametrize("layout", LAYOUTS)
def test_sequence_cache_counts_a_decode_step_by_its_positions(layout):
    """What a decode step reads, holds and updates, counted from the
    step's positions alone: the closed forms over the running rows, by
    ``pages_for``."""
    from ray_tpu.llm.kv_cache import pages_for

    cache, spec = _sequence_cache(layout, num_pages=64, max_batch=5)
    page, want = 4, {}
    steps = ([0, -1, 3, -1, 4], [7, 8, -1, 11, 12], [-1, 31, -1, -1, -1],
             [255, 1, 2, 3, 200])
    for cached in steps:
        positions = np.full((5, 1), -1, np.int32)
        positions[:, 0] = cached
        cache.count_decode(positions)
        rows = [n for n in cached if n >= 0]
        full = sum(pages_for(n + 1, page) for n in rows)
        adds = {"decode_runs": 1,
                "kv_rows_read": full * page * spec.kv_layers,
                "kv_rows_held":
                    5 * cache.pages_per_seq * page * spec.kv_layers}
        if spec.window_layers:
            kept = sum(pages_for(min(n + 1, spec.window), page)
                       for n in rows)
            per = page * spec.window_layers
            held = 5 * cache.ring_pages * per
            adds["kv_rows_read"] += kept * per
            adds["kv_rows_held"] += held
            adds.update(window_rows_read=kept * per, window_rows_held=held,
                        window_positions_dropped=(full - kept) * per)
        if spec.state_layers:
            adds["state_rows_updated"] = len(rows) * spec.state_layers
        for key, n in adds.items():
            want[key] = want.get(key, 0) + n
    stats = cache.stats()
    got = {**stats["attention"], **stats.get("state", {})}
    assert {key: got[key] for key in want} == want
    assert all(type(got[key]) is int for key in want)


# ----------------------------------------------- decode-mode identity


def _decode_loop(model, params, cfg, n_kv_head, prompt, steps,
                 page_size=4, pad_to=16):
    """Greedy generation through the paged decode path; returns
    (tokens, per-step last-position logits)."""
    import jax.numpy as jnp

    from ray_tpu.llm.kv_cache import init_cache, pages_for

    n = len(prompt)
    kv = init_cache(cfg.n_layer, 32, page_size, n_kv_head,
                    cfg.d_model // cfg.n_head, cfg.dtype)
    P = pages_for(cfg.max_seq, page_size)
    pages = list(range(pages_for(n, page_size)))
    table = np.zeros((1, P), np.int32)
    table[0, :len(pages)] = pages
    tokens = np.zeros((1, pad_to), np.int32)
    tokens[0, :n] = prompt
    pos = np.full((1, pad_to), -1, np.int32)
    pos[0, :n] = np.arange(n)
    logits, kv = model.apply(
        params, jnp.asarray(tokens),
        kv_cache={"k_pages": kv["k_pages"], "v_pages": kv["v_pages"],
                  "page_table": jnp.asarray(table)},
        positions=jnp.asarray(pos))
    out_logits = [np.asarray(logits[0, n - 1])]
    cur = int(np.argmax(out_logits[0]))
    out, cached = [cur], n
    for _ in range(steps - 1):
        while cached // page_size + 1 > len(pages):
            pages.append(len(pages))
            table[0, :len(pages)] = pages
        logits, kv = model.apply(
            params, np.asarray([[cur]], np.int32),
            kv_cache={"k_pages": kv["k_pages"],
                      "v_pages": kv["v_pages"],
                      "page_table": jnp.asarray(table)},
            positions=np.asarray([[cached]], np.int32))
        cached += 1
        out_logits.append(np.asarray(logits[0, 0]))
        cur = int(np.argmax(logits[0, 0]))
        out.append(cur)
    return out, out_logits


def _full_forward_loop(model, params, prompt, steps):
    import jax.numpy as jnp

    toks, logits_out = list(prompt), []
    for _ in range(steps):
        logits = model.apply(params, jnp.asarray([toks], jnp.int32))
        logits_out.append(np.asarray(logits[0, -1]))
        toks.append(int(np.argmax(logits_out[-1])))
    return toks[len(prompt):], logits_out


def test_gpt2_incremental_decode_token_identical():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt2 import GPT2, GPT2Config, gpt2_init

    cfg = dataclasses.replace(GPT2Config.tiny(), remat=False,
                              dtype=jnp.float32)
    params = gpt2_init(cfg, jax.random.PRNGKey(0))
    model = GPT2(cfg)
    prompt = [3, 17, 42, 99, 7]
    ref, ref_logits = _full_forward_loop(model, params, prompt, 6)
    dec, dec_logits = _decode_loop(model, params, cfg, cfg.n_head,
                                   prompt, 6)
    assert dec == ref
    for a, b in zip(ref_logits, dec_logits):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


# program -> sha256[:16] of the lowered text (``lower().as_text()``, no
# debug info) of GPT-2's tiny preset through ``jit_forward`` over 16 pages
# of 4 and a table of 8 pages, read from PR 48's tree, the parent of the
# PR that taught the training block what to keep across its remat boundary.
GPT2_PARENT_TEXT = {"prefill": ((1, 16), "f422f1cbaf690829"),
                    "decode": ((2, 1), "57c792f3691bae6e")}


@pytest.mark.parametrize("program", sorted(GPT2_PARENT_TEXT))
def test_gpt2_cached_forward_lowers_to_the_parents_text(program):
    """What a remat'd block keeps is named on the training path alone
    (``cache is None``): the serving programs, the prefill as the engine
    calls it (``last``) and the decode step, lower to the text they
    lowered to before, letter for letter."""
    import hashlib

    import jax.numpy as jnp

    from ray_tpu.llm.engine import jit_forward
    from ray_tpu.llm.kv_cache import init_pool, pool_arrays
    from ray_tpu.models import MODEL_FAMILIES

    fam = MODEL_FAMILIES["gpt2"]
    cfg = dataclasses.replace(fam.tiny(), remat=False)
    params = jax.eval_shape(lambda: fam.init(cfg, jax.random.PRNGKey(0)))
    spec = fam.cache(cfg)
    kv = jax.eval_shape(lambda: init_pool(spec, 16, 4, cfg.dtype))
    shape, want = GPT2_PARENT_TEXT[program]
    ints = jax.ShapeDtypeStruct(shape, jnp.int32)
    served = {} if shape[1] == 1 else {
        "last": jax.ShapeDtypeStruct(shape[:1], jnp.int32)}
    text = jit_forward(fam.module(cfg)).lower(
        params, ints, *[kv[k] for k in pool_arrays(spec)],
        jax.ShapeDtypeStruct((shape[0], 8), jnp.int32), ints,
        **served).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == want


def test_llama_incremental_decode_token_identical():
    """GQA cache (h_kv < h) + positional RoPE through the paged path."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import Llama, LlamaConfig, llama_init

    cfg = dataclasses.replace(LlamaConfig.tiny(), remat=False,
                              dtype=jnp.float32)
    assert cfg.n_kv_head < cfg.n_head   # the GQA path is the point
    params = llama_init(cfg, jax.random.PRNGKey(1))
    model = Llama(cfg)
    prompt = [3, 17, 42, 99, 7, 250, 8]
    ref, ref_logits = _full_forward_loop(model, params, prompt, 5)
    dec, dec_logits = _decode_loop(model, params, cfg, cfg.n_kv_head,
                                   prompt, 5)
    assert dec == ref
    for a, b in zip(ref_logits, dec_logits):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_cached_forward_goes_through_the_one_attention_core(
        family, monkeypatch):
    """Both blocks hand the paged cache to models/attention.py: each
    layer stores once, from there and nowhere else; a PREFILL then
    attends among its own rows and reads nothing from the pool (neither
    ``paged_attend`` nor the kernel is called), a decode step attends
    once: through ``paged_attend`` on this backend and, where the
    dispatch says kernel (the ``tpu`` backend's rule, by q's shape),
    through ``paged_decode``, to the same tokens."""
    import sys

    import jax

    import ray_tpu.models.attention as attention
    from ray_tpu.llm import kv_cache
    from ray_tpu.models import MODEL_FAMILIES
    from ray_tpu.ops import paged_attention

    callers = []
    for module, name in ((kv_cache, "paged_store"),
                         (kv_cache, "paged_attend"),
                         (paged_attention, "paged_decode")):
        real = getattr(module, name)

        def spy(*args, _real=real, _name=name, **kwargs):
            callers.append(
                (_name, sys._getframe(1).f_globals["__name__"]))
            return _real(*args, **kwargs)

        monkeypatch.setattr(module, name, spy)
    fam = MODEL_FAMILIES[family]
    cfg = dataclasses.replace(fam.tiny(), remat=False)
    params = fam.init(cfg, jax.random.PRNGKey(0))

    def two_steps():        # a prefill, then one decode step
        del callers[:]
        return _decode_loop(fam.module(cfg), params, cfg,
                            fam.cache(cfg).kv_heads, [3, 17, 42], 2)[0]

    store = ("paged_store", "ray_tpu.models.attention")

    def layers(attend):     # the prefill's stores, then the decode step
        return [store] * cfg.n_layer + [
            store, (attend, "ray_tpu.models.attention")] * cfg.n_layer

    tokens = two_steps()
    assert callers == layers("paged_attend")
    monkeypatch.setattr(attention, "_decode_kernel",
                        lambda q, k_pages: q.shape[1] == 1)
    assert two_steps() == tokens
    assert callers == layers("paged_decode")


# --------------------------------------------- the pool stays in place


@pytest.mark.parametrize("family", ["gpt2", "llama_gqa", "olmoe"])
def test_forward_updates_donated_pool_in_place(family):
    """The engine's jitted forward aliases BOTH pool arrays to its
    outputs and holds no second pool among its temporaries: the model
    carries one [L, pages, page, h_kv*d] pool through the layers and
    scatters into it.  Slicing a layer out and stacking the layers
    again put 1.80-1.94 pool arrays of temporaries here; carried, the
    four programs below hold 0.09-0.32 (activations).
    float32 on purpose: the CPU backend upcasts a bf16 scatter to f32
    and would put a pool-sized temporary back that the TPU never has
    (tests/test_tpu_compile.py holds the TPU's program to the same)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm.engine import jit_forward
    from ray_tpu.llm.kv_cache import init_cache, pages_for

    if family == "gpt2":
        from ray_tpu.models.gpt2 import GPT2, GPT2Config, gpt2_init

        cfg = dataclasses.replace(GPT2Config.tiny(), n_layer=4,
                                  remat=False, dtype=jnp.float32)
        model, init, n_kv_head = GPT2(cfg), gpt2_init, cfg.n_head
    else:
        from ray_tpu.models.llama import Llama, LlamaConfig, llama_init

        tiny = LlamaConfig.tiny if family == "llama_gqa" \
            else LlamaConfig.olmoe_tiny     # experts, and a 4th output
        cfg = dataclasses.replace(tiny(), n_layer=4, remat=False,
                                  dtype=jnp.float32)
        assert (cfg.n_kv_head < cfg.n_head) == (family == "llama_gqa")
        model, init, n_kv_head = Llama(cfg), llama_init, cfg.n_kv_head
    page_size = 16
    # The CPU lowers a grouped matmul to a dense one over all experts:
    # ~0.9 MB of temporaries that do not grow with the pool, so the
    # OLMoE case takes a pool large enough to tell the two apart.
    num_pages = 256 if family == "olmoe" else 64
    params = jax.eval_shape(lambda: init(cfg, jax.random.PRNGKey(0)))
    kv = jax.eval_shape(lambda: init_cache(
        cfg.n_layer, num_pages, page_size, n_kv_head,
        cfg.d_model // cfg.n_head, cfg.dtype))
    pool_bytes = kv["k_pages"].size * kv["k_pages"].dtype.itemsize
    fwd = jit_forward(model)
    for shape in ((2, 1), (1, 32)):          # decode, prefill
        ints = jax.ShapeDtypeStruct(shape, jnp.int32)
        table = jax.ShapeDtypeStruct(
            (shape[0], pages_for(cfg.max_seq, page_size)), jnp.int32)
        m = fwd.lower(params, ints, kv["k_pages"], kv["v_pages"], table,
                      ints).compile().memory_analysis()
        assert m.alias_size_in_bytes == 2 * pool_bytes, shape
        assert m.temp_size_in_bytes < 0.5 * pool_bytes, (
            shape, m.temp_size_in_bytes / pool_bytes)


def test_a_window_alone_is_the_whole_donated_state_pool():
    """A model whose recurrent layers keep a conv window and NO state
    (models/lfm2.py, ``ssm_shape == ()``): ``init_state`` builds no
    ``ssm`` array, the forward takes and returns ``conv`` alone, and it
    is aliased to the output as the pages are."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm.engine import jit_forward
    from ray_tpu.llm.kv_cache import (init_cache, init_state, pages_for,
                                      state_arrays)
    from ray_tpu.models import MODEL_FAMILIES

    fam = MODEL_FAMILIES["lfm2moe"]
    cfg = dataclasses.replace(fam.tiny(), remat=False)
    spec = fam.cache(cfg)
    assert state_arrays(spec) == ("conv",)
    assert state_arrays(MODEL_FAMILIES["granitemoehybrid"].cache(
        MODEL_FAMILIES["granitemoehybrid"].tiny())) == ("conv", "ssm")
    assert state_arrays(MODEL_FAMILIES["gpt2"].cache(
        MODEL_FAMILIES["gpt2"].tiny())) == ()
    params = jax.eval_shape(lambda: fam.init(cfg, jax.random.PRNGKey(0)))
    kv = jax.eval_shape(lambda: init_cache(
        spec.kv_layers, 64, 16, spec.kv_heads, spec.head_dim, cfg.dtype))
    state = jax.eval_shape(lambda: init_state(spec, 256, cfg.dtype))
    assert list(state) == ["conv"] and state["conv"].shape == (4, 256, 2, 64)
    ints = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    compiled = jit_forward(fam.module(cfg)).lower(
        params, ints, kv["k_pages"], kv["v_pages"],
        jax.ShapeDtypeStruct((2, pages_for(cfg.max_seq, 16)), jnp.int32),
        ints, state["conv"], jax.ShapeDtypeStruct((2,), jnp.int32)).compile()
    pools = (kv["k_pages"], kv["v_pages"], state["conv"])
    assert compiled.memory_analysis().alias_size_in_bytes == sum(
        a.size * a.dtype.itemsize for a in pools)
    # logits, the three pools, the routing counters of the 3 sparse layers
    assert [o.shape for o in jax.tree_util.tree_leaves(
        compiled.out_info)][-1] == (3, 4)


@pytest.mark.parametrize("shape", [(2, 1), (1, 32), (1, 8)],
                         ids=["decode", "prefill_chunks", "prefill_1chunk"])
def test_forward_updates_the_donated_state_pool_in_place(shape):
    """The second kind of cache as the first: a model with recurrent
    layers (models/granite.py) takes the state pool's ``conv`` and
    ``ssm`` donated, carries them whole through its layers and writes
    each row's slot where it lies: all four arrays are aliased to the
    outputs (a decode step; a prefill of several chunks, 32 positions in
    chunks of 8, and of one), and the decode step's temporaries do not
    grow with the state pool.  (A prefill's do HERE: the CPU's compiler
    copies the pool around the one-row loops that read and write the
    prefill's slot; the TPU's programs, which hold no such copy, are
    pinned in tests/test_tpu_compile.py.)"""
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm.engine import jit_forward
    from ray_tpu.llm.kv_cache import init_cache, init_state, pages_for
    from ray_tpu.models import MODEL_FAMILIES

    fam = MODEL_FAMILIES["granitemoehybrid"]
    cfg = dataclasses.replace(fam.tiny(), remat=False,
                              layer_types=("mamba", "attention") * 2
                              + ("mamba",) * 2)
    spec = fam.cache(cfg)
    assert (spec.kv_layers, spec.state_layers) == (2, 4)
    params = jax.eval_shape(lambda: fam.init(cfg, jax.random.PRNGKey(0)))
    kv = jax.eval_shape(lambda: init_cache(
        spec.kv_layers, 64, 16, spec.kv_heads, spec.head_dim, cfg.dtype))
    state = jax.eval_shape(lambda: init_state(spec, 256, cfg.dtype))
    assert state["ssm"].shape == (4, 256, 4, 16, 16)
    assert state["conv"].shape == (4, 256, 3, 96)

    def nbytes(a):
        return a.size * a.dtype.itemsize

    state_bytes = nbytes(state["conv"]) + nbytes(state["ssm"])
    ints = jax.ShapeDtypeStruct(shape, jnp.int32)
    m = jit_forward(fam.module(cfg)).lower(
        params, ints, kv["k_pages"], kv["v_pages"],
        jax.ShapeDtypeStruct((shape[0], pages_for(cfg.max_seq, 16)),
                             jnp.int32), ints, state["conv"],
        state["ssm"], jax.ShapeDtypeStruct(shape[:1], jnp.int32)
    ).compile().memory_analysis()
    assert m.alias_size_in_bytes == 2 * nbytes(kv["k_pages"]) + state_bytes
    if shape[1] == 1:
        assert m.temp_size_in_bytes < 0.5 * nbytes(state["ssm"]), (
            shape, m.temp_size_in_bytes / nbytes(state["ssm"]))


# --------------------------------------------------- rope table cache


def test_rope_tables_cached_and_equivalent():
    import jax.numpy as jnp

    from ray_tpu.models.layers import _rope, _rope_tables

    a_cos, a_sin = _rope_tables(32, 16, 10000.0)
    b_cos, b_sin = _rope_tables(32, 16, 10000.0)
    assert a_cos is b_cos and a_sin is b_sin   # cache hit, same object
    # Table values match the closed form.
    half = 8
    freqs = 10000.0 ** (-np.arange(half, dtype=np.float32) / half)
    angles = np.arange(32, dtype=np.float32)[:, None] * freqs[None, :]
    np.testing.assert_allclose(np.asarray(a_cos), np.cos(angles),
                               rtol=1e-6)
    # Positional rope at contiguous positions == table-driven rope.
    x = np.random.default_rng(0).normal(
        size=(2, 8, 2, 16)).astype(np.float32)
    base = _rope(jnp.asarray(x), 10000.0)
    pos = np.broadcast_to(np.arange(8, dtype=np.int32), (2, 8))
    with_pos = _rope(jnp.asarray(x), 10000.0, jnp.asarray(pos))
    np.testing.assert_allclose(np.asarray(base), np.asarray(with_pos),
                               rtol=1e-5, atol=1e-6)


# ------------------------------------------------- decode flops helper


def test_decode_flops_per_token():
    from ray_tpu.models.gpt2 import GPT2Config
    from ray_tpu.models.llama import LlamaConfig

    for cfg in (GPT2Config.small(), LlamaConfig.llama2_7b()):
        train = cfg.flops_per_token()
        dec0 = cfg.decode_flops_per_token(0)
        dec_full = cfg.decode_flops_per_token(cfg.max_seq)
        # Forward-only: well under half the 6ND training count even at
        # full context (claiming decode MFU with 6ND is the lie the
        # helper exists to prevent).
        assert 0 < dec_full < train / 2.5
        # Attention cost grows linearly with context.
        assert dec_full > dec0
        mid = cfg.decode_flops_per_token(cfg.max_seq // 2)
        assert dec0 < mid < dec_full
        # Default context is max_seq/2.
        assert cfg.decode_flops_per_token() == pytest.approx(mid)
    # GQA shrinks KV projections but not attention arithmetic: a
    # Llama with fewer KV heads has strictly fewer decode FLOPs.
    full = LlamaConfig(n_kv_head=8)
    gqa = LlamaConfig(n_kv_head=2)
    assert gqa.decode_flops_per_token() < full.decode_flops_per_token()


# ------------------------------------------------- telemetry surfacing


def test_cluster_summary_collects_llm_metrics(monkeypatch):
    from ray_tpu.util import state as state_api
    from ray_tpu.util import telemetry as telemetry_mod

    def g(name, value):
        return {"name": name, "kind": "gauge", "description": "",
                "series": [{"tags": {}, "value": value}]}

    sources = {
        "replica-1": [g("rt_llm_kv_pages_used", 5.0),
                      g("rt_llm_kv_pages_total", 64.0),
                      g("rt_llm_batch_size", 3.0),
                      g("rt_llm_tokens_total", 120.0)],
        "replica-2": [g("rt_llm_kv_pages_used", 2.0),
                      g("rt_llm_kv_pages_total", 64.0),
                      g("rt_llm_batch_size", 1.0),
                      g("rt_llm_evictions_total", 4.0)],
    }
    monkeypatch.setattr(
        state_api, "telemetry",
        lambda address=None: {"ts": 0.0, "sources": sources,
                              "flight": []})
    monkeypatch.setattr(state_api, "metrics_history",
                        lambda address=None: {})
    monkeypatch.setattr(
        state_api, "serve_resilience",
        lambda address=None: (_ for _ in ()).throw(RuntimeError))
    summary = telemetry_mod.cluster_summary()
    llm = summary["llm"]
    assert llm["kv_pages_used"] == 7.0
    assert llm["kv_pages_total"] == 128.0
    assert llm["engines"] == 2
    assert llm["batch_size"] == 4.0
    assert llm["tokens"] == 120.0
    assert llm["evictions"] == 4.0
    text = telemetry_mod.render_text(summary)
    assert "LLM engine" in text
    assert "7 / 128 pages" in text
    assert "evictions" in text


def test_render_text_omits_llm_section_when_absent():
    from ray_tpu.util.telemetry import render_text

    text = render_text({"goodput": {}, "llm": {
        "kv_pages_used": 0.0, "kv_pages_total": 0.0, "batch_size": 0.0,
        "waiting": 0.0, "tokens": 0.0, "prefill_tokens": 0.0,
        "evictions": 0.0, "engines": 0}})
    assert "LLM engine" not in text
