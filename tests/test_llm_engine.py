"""Continuous-batching engine (no cluster): the tier-1 decode smoke
(prefill + decode steps through the engine, token-identical to the
non-cached full forward), step-granularity admission with no batch
barrier, disconnect eviction returning the page-pool gauge to
baseline, recompute preemption under KV pressure, and scheduler
units."""

import dataclasses
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.llm.engine import EngineConfig, GenerationEngine, _bucket
from ray_tpu.llm.sampling import SamplingParams
from ray_tpu.models.gpt2 import GPT2, GPT2Config, gpt2_init

CFG = dataclasses.replace(GPT2Config.tiny(), remat=False,
                          dtype=jnp.float32)


@pytest.fixture(scope="module")
def setup():
    """One shared tiny model + engine (compiles once for the module)."""
    params = gpt2_init(CFG, jax.random.PRNGKey(3))
    eng = GenerationEngine(
        model_cfg=CFG,
        engine_cfg=EngineConfig(page_size=4, num_pages=64, max_batch=4,
                                prefill_token_budget=64,
                                max_tokens_default=8),
        params=params).start()
    yield eng, params
    eng.stop()


def _reference(params, prompt, steps):
    model = GPT2(CFG)
    toks = list(prompt)
    for _ in range(steps):
        logits = model.apply(params, jnp.asarray([toks], jnp.int32))
        toks.append(int(np.argmax(np.asarray(logits[0, -1]))))
    return toks[len(prompt):]


def test_engine_smoke_token_identical(setup):
    """Tier-1 smoke: prefill + a few decode steps through the engine
    produce exactly the non-cached full forward's greedy tokens."""
    eng, params = setup
    prompt = [5, 100, 23, 77]
    assert eng.generate(prompt, max_tokens=6) == \
        _reference(params, prompt, 6)


# prompt -> its six greedy tokens as the parent of PR 46 served them (the
# all-rows prefill and the per-bucket picker ``llm_last[bucket]``): a
# prompt that fills its bucket (8, 16) and one that ends inside it.
PARENTS_GREEDY = {(5, 100, 23): [23] * 6, tuple(range(40, 48)): [47] * 6,
                  tuple(range(7, 18)): [97] * 6,
                  tuple(range(300, 316)): [315] * 6}


@pytest.mark.parametrize("prompt", PARENTS_GREEDY,
                         ids=[f"prompt_of_{len(p)}" for p in PARENTS_GREEDY])
def test_a_prefill_that_serves_one_position_gives_the_parents_tokens(
        setup, prompt):
    """The prefill hands the forward the prompt's last position and gets
    [1, 1, V] back: the greedy tokens are the ones the all-rows prefill
    gave, which are the full forward's; no program of the bucket's size
    picks a row."""
    eng, params = setup
    served = eng.generate(list(prompt), max_tokens=6)
    assert served == PARENTS_GREEDY[prompt] == _reference(params, prompt, 6)
    programs = eng.stats()["programs"]
    prefill = f"llm_prefill[{_bucket(len(prompt))}]"
    assert {prefill, "llm_last"} <= set(programs)
    assert not [name for name in programs if name.startswith("llm_last[")]
    logits = jax.tree_util.tree_leaves(
        eng._exe_cache[prefill][1].out_info)[0]
    assert logits.shape == (1, 1, CFG.vocab_size)


def test_mid_flight_admission_no_batch_barrier(setup):
    """A sequence submitted while another is mid-generation starts
    decoding before the first finishes — step-granularity admission,
    the continuous-batching property."""
    eng, params = setup
    a = eng.submit([9, 4, 300], max_tokens=40)
    it_a = eng.frames(a)
    first_a = [next(it_a) for _ in range(3)]     # a is mid-flight
    assert all("token" in f for f in first_a)
    b = eng.submit([8, 8, 8], max_tokens=3)
    b_frames = list(eng.frames(b))
    # b ran to completion while a was still generating: no barrier.
    assert not a.finished
    assert [f["token"] for f in b_frames if "token" in f] == \
        _reference(params, [8, 8, 8], 3)
    assert b_frames[-1] == {"done": True, "reason": "length",
                            "n_tokens": 3}
    rest = list(it_a)
    assert rest[-1].get("done")
    # a's output was unaffected by b coming and going.
    toks_a = [f["token"] for f in first_a + rest if "token" in f]
    assert toks_a == _reference(params, [9, 4, 300], 40)


def test_cancel_mid_stream_frees_kv_pages_to_baseline(setup):
    """Disconnect eviction: cancelling a mid-flight sequence removes it
    from the running batch and returns the page-pool gauge to its
    baseline."""
    from ray_tpu.util.metrics import registry

    def gauge():
        for snap in registry().snapshot():
            if snap["name"] == "rt_llm_kv_pages_used":
                return snap["series"][0]["value"]
        return None

    eng, _ = setup
    baseline = eng.cache.pools["full"].used
    assert baseline == 0
    seq = eng.submit([1, 2, 3], max_tokens=500)
    it = eng.frames(seq)
    next(it)
    next(it)
    assert eng.cache.pools["full"].used > baseline   # held mid-stream
    eng.cancel(seq.sid)
    frames = list(it)
    assert frames[-1] == {"done": True, "reason": "cancelled",
                          "n_tokens": seq.generated}
    deadline = time.time() + 10
    while time.time() < deadline and eng.cache.pools["full"].used != baseline:
        time.sleep(0.05)
    assert eng.cache.pools["full"].used == baseline
    assert gauge() == float(baseline)
    assert eng.stats()["running"] == 0


def test_eviction_recompute_preserves_greedy_output():
    """KV pressure: a pool too small for two full sequences forces
    recompute preemption — both still produce exactly the reference
    greedy tokens, nothing is re-emitted, and all pages free."""
    params = gpt2_init(CFG, jax.random.PRNGKey(3))
    eng = GenerationEngine(
        model_cfg=CFG,
        engine_cfg=EngineConfig(page_size=4, num_pages=10, max_batch=4),
        params=params).start()
    try:
        a = eng.submit([5, 100, 23, 77], max_tokens=20)
        b = eng.submit([9, 4, 300], max_tokens=20)
        toks_a = [f["token"] for f in eng.frames(a) if "token" in f]
        toks_b = [f["token"] for f in eng.frames(b) if "token" in f]
        assert toks_a == _reference(params, [5, 100, 23, 77], 20)
        assert toks_b == _reference(params, [9, 4, 300], 20)
        st = eng.stats()
        assert st["evictions"] > 0
        assert st["kv_pages_used"] == 0
    finally:
        eng.stop()


def test_seeded_sampling_reproducible(setup):
    eng, _ = setup
    p = SamplingParams(temperature=0.9, top_k=50)
    one = eng.generate([10, 20, 30], max_tokens=6, params=p, seed=42)
    two = eng.generate([10, 20, 30], max_tokens=6, params=p, seed=42)
    other = eng.generate([10, 20, 30], max_tokens=6, params=p, seed=43)
    assert one == two
    assert len(one) == 6
    assert other != one or True   # different seed may coincide; no pin


SAMPLED = SamplingParams(temperature=0.9, top_k=50, top_p=0.95)


def _tokens(eng, seq):
    return [f["token"] for f in eng.frames(seq) if "token" in f]


@pytest.mark.parametrize("company", ["full_batch", "later_slot",
                                     "recompute_preemption"])
def test_seeded_request_gives_the_same_tokens_in_any_company(setup,
                                                             company):
    """The draw's key is (the request's seed, the token's index): alone,
    among a full batch of other requests, in another slot, or evicted
    and re-prefilled, a request gives the same tokens."""
    eng, params = setup
    prompt, seed = [10, 20, 30], 4242 + (1 << 35)
    alone = eng.generate(prompt, max_tokens=12, params=SAMPLED, seed=seed)
    assert len(alone) == 12
    if company == "recompute_preemption":
        eng = GenerationEngine(
            model_cfg=CFG, params=params,
            engine_cfg=EngineConfig(page_size=4, num_pages=8,
                                    max_batch=4)).start()
    try:
        others = [([7, 8, 9, 11], SamplingParams(temperature=1.1), 5),
                  ([300, 2], SamplingParams(), None),
                  ([40, 41, 42, 43, 44], SAMPLED, seed + 1)]
        if company == "full_batch":
            seqs = [eng.submit(prompt, 12, SAMPLED, seed)] + [
                eng.submit(p, 12, sp, sd) for p, sp, sd in others]
        else:
            seqs = [eng.submit(p, 12, sp, sd) for p, sp, sd in others[:2]]
            if company == "later_slot":     # the others are decoding
                it = eng.frames(seqs[0])
                next(it), next(it)
            seqs.insert(0, eng.submit(prompt, 12, SAMPLED, seed))
        assert _tokens(eng, seqs[0]) == alone
        for seq in seqs[1:]:
            list(eng.frames(seq))
        if company == "recompute_preemption":
            assert eng.stats()["evictions"] > 0
    finally:
        if company == "recompute_preemption":
            eng.stop()


def test_one_sampler_program_for_every_mix_and_ids_to_the_host():
    """warmup() pays every compile a mixed batch needs; what a decode
    step hands the host is [max_batch] int32, pinned on the sampler
    program's own output; stats()["sampling"] counts what was packed."""
    params = gpt2_init(CFG, jax.random.PRNGKey(3))
    eng = GenerationEngine(
        model_cfg=CFG, params=params,
        engine_cfg=EngineConfig(page_size=4, num_pages=64, max_batch=4))
    eng.warmup()
    warm = eng.stats()
    # (the placement of a prefill's row is ONE program, not one a bucket)
    assert set(warm["programs"]) == {"llm_prefill[8]", "llm_last",
                                     "llm_sample", "llm_feed",
                                     "llm_decode"}
    served = jax.tree_util.tree_leaves(
        eng._exe_cache["llm_prefill[8]"][1].out_info)[0]
    assert served.shape == (1, 1, CFG.vocab_size)   # not [1, 8, V]
    assert warm["sampling"] == {"rows_greedy": 2, "rows_sampled": 0,
                                "steps": 2, "steps_sampled": 0}
    out, = jax.tree_util.tree_leaves(
        eng._exe_cache["llm_sample"][1].out_info)
    assert (out.shape, out.dtype) == ((4,), np.int32)
    fetched = jax.tree_util.tree_leaves(
        eng._exe_cache["llm_decode"][1].out_info)[0]
    assert fetched.shape == (4, 1, CFG.vocab_size)  # stays on the device

    eng.start()
    try:
        seqs = [eng.submit([5, 6, 7], 6, SamplingParams(), None),
                eng.submit([8, 9], 6, SAMPLED, 1),
                eng.submit([1, 2, 3, 4], 6,
                           SamplingParams(temperature=0.5, top_p=0.5), 2)]
        for seq in seqs:
            assert len(_tokens(eng, seq)) == 6
    finally:
        eng.stop()
    after = eng.stats()
    assert after["compiles"] == warm["compiles"] == 5
    assert after["programs"] == warm["programs"]
    counts = after["sampling"]
    assert counts["rows_greedy"] == 2 + 6 and counts["rows_sampled"] == 12
    assert counts["rows_greedy"] + counts["rows_sampled"] == \
        after["tokens_generated"]
    # every launch after the warm-up held a sampled row
    assert counts["steps"] - counts["steps_sampled"] <= 2 + 1
    assert counts["steps_sampled"] >= 5


def test_submit_rejects_bad_requests(setup):
    eng, _ = setup
    with pytest.raises(ValueError):
        eng.submit([])
    with pytest.raises(ValueError):
        eng.submit([CFG.vocab_size + 5])
    with pytest.raises(ValueError):
        eng.submit(list(range(eng.cache.max_context)))   # no room to decode
    with pytest.raises(ValueError):
        eng.submit([1], params=SamplingParams(top_p=2.0))


def test_step_failure_poisons_inflight_but_engine_survives(setup):
    """A failing engine step error-retires the in-flight sequences but
    the loop keeps running — the replica stays serviceable instead of
    bricking on one transient forward failure (review finding)."""
    eng, params = setup
    real_fwd = eng._fwd

    class Boom:
        def lower(self, *a, **k):
            raise RuntimeError("injected step failure")

    eng._fwd = Boom()
    try:
        frames = list(eng.frames(eng.submit([1, 2, 3], max_tokens=5)))
        assert "error" in frames[-1]
        assert "injected step failure" in frames[-1]["error"]
    finally:
        eng._fwd = real_fwd
    # Pages freed, error accounted, and the NEXT request works.
    st = eng.stats()
    assert st["step_errors"] >= 1
    assert st["kv_pages_used"] == 0
    assert eng.generate([5, 100, 23, 77], max_tokens=4) == \
        _reference(params, [5, 100, 23, 77], 4)


def test_prefill_bucketing():
    assert _bucket(1) == 8
    assert _bucket(8) == 8
    assert _bucket(9) == 16
    assert _bucket(100) == 128


def test_length_cap_at_max_context():
    """A generation that would outrun the context window retires with
    reason "length" at the cap instead of writing past the page
    table."""
    params = gpt2_init(CFG, jax.random.PRNGKey(3))
    eng = GenerationEngine(
        model_cfg=CFG,
        engine_cfg=EngineConfig(page_size=4, num_pages=16, max_batch=2,
                                max_context=16),
        params=params).start()
    try:
        frames = list(eng.frames(eng.submit([1, 2, 3, 4],
                                            max_tokens=1000)))
        assert frames[-1]["reason"] == "length"
        # Cache slots: prompt (4) + fed generated tokens fill exactly
        # the 16-slot window; the final sampled token is emitted but
        # never cached -> 16 - 4 + 1 generated.
        assert frames[-1]["n_tokens"] == 13
        assert eng.stats()["kv_pages_used"] == 0
    finally:
        eng.stop()


# --------------------------------------------- the depth-one pipeline

def _family_engine(family, **engine):
    """A tiny float32 model of ``family`` with weights large enough that
    the greedy tokens vary, and an engine over it."""
    from ray_tpu.models import MODEL_FAMILIES

    fam = MODEL_FAMILIES[family]
    cfg = dataclasses.replace(fam.tiny(), remat=False, dtype=jnp.float32)
    params = jax.tree_util.tree_map(
        lambda x: x * 6.0, fam.init(cfg, jax.random.PRNGKey(5)))
    return GenerationEngine(
        model=family, model_cfg=cfg, params=params,
        engine_cfg=EngineConfig(**{**dict(page_size=4, num_pages=64,
                                          max_batch=3), **engine}))


MIXED = [([3, 17, 42, 7, 99, 5, 23, 11, 2, 64, 31, 8, 90, 12, 55, 71, 6,
           19], 9), ([9, 4], 14), ([80, 1, 33, 27, 60], 1),
         ([7, 7, 7], 6), ([100, 2, 50, 4, 25, 8], 11), ([61], 2),
         ([12, 13, 14, 15, 16, 17, 18, 19, 20], 5)]


def _step_until_done(eng, seqs):
    while not all(s.finished for s in seqs):
        eng.step()
    assert eng.stats()["step_errors"] == 0, eng.stats()["last_error"]
    return [s.tokens[s.prompt_len:] for s in seqs]


@pytest.mark.parametrize("family", ["gpt2", "olmoe", "granitemoehybrid",
                                    "lfm2moe"])
def test_pipelined_batch_gives_what_each_request_gives_alone(family):
    """Seven greedy requests of mixed lengths over three rows (rows
    change hands while others run; one ends at its prefill): token for
    token what each gives alone, every frame in order, ``done`` after
    the last token; nearly every program was launched ahead and no row's
    result was dropped (no EOS: every length is known at launch)."""
    eng = _family_engine(family).start()
    try:
        alone = [eng.generate(p, max_tokens=n) for p, n in MIXED]
        assert [len(t) for t in alone] == [n for _, n in MIXED]
        assert len({t for out in alone for t in out}) > 6
        before = eng.stats()
        seqs = [eng.submit(p, max_tokens=n) for p, n in MIXED]
        frames = [list(eng.frames(s)) for s in seqs]
    finally:
        eng.stop()
    for fr, want in zip(frames, alone):
        assert [f["token"] for f in fr[:-1]] == want
        assert [f["index"] for f in fr[:-1]] == list(range(len(want)))
        assert fr[-1] == {"done": True, "reason": "length",
                          "n_tokens": len(want)}
    after = eng.stats()
    pipe, was = after["pipeline"], before["pipeline"]
    assert pipe["rows_discarded"] == 0
    assert pipe["drains"]["evict"] == pipe["drains"]["error"] == 0
    programs = (after["steps"] - before["steps"]) \
        + (after["prefills"] - before["prefills"])
    drains = pipe["drains"]["empty"] - was["drains"]["empty"]
    assert 1 <= drains <= 3
    assert pipe["launched_ahead"] - was["launched_ahead"] >= \
        programs - 2 * drains - 1
    assert after["kv_pages_used"] == 0 and eng.cache.slots.used == 0
    assert after["tokens_generated"] - before["tokens_generated"] == \
        sum(n for _, n in MIXED)


def test_eos_mid_batch_costs_one_row_step_and_frees_the_row():
    """EOS is the one end not known at launch: the row was launched once
    more, that result is dropped (never a frame after EOS), its pages and
    slot go back at the EOS's delivery, and the request that takes the
    row next is served correctly."""
    plain = _family_engine("granitemoehybrid")
    seqs = [plain.submit(p, max_tokens=n) for p, n in MIXED]
    streams = _step_until_done(plain, seqs)
    # a token in the middle of the longest stream
    eos = streams[1][6]
    want = [s[:s.index(eos) + 1] if eos in s else s for s in streams]
    cut_short = sum(len(w) < len(s) for w, s in zip(want, streams))
    assert cut_short >= 1 and any(eos not in s for s in streams)

    eng = _family_engine("granitemoehybrid", eos_id=eos)
    seqs = [eng.submit(p, max_tokens=n) for p, n in MIXED]
    assert _step_until_done(eng, seqs) == want
    for seq, tokens, full in zip(seqs, want, streams):
        frames = []
        while not seq.out.empty():
            frames.append(seq.out.get())
        assert [f["token"] for f in frames[:-1]] == tokens
        ended = "eos" if tokens[-1] == eos else "length"
        assert frames[-1] == {"done": True, "reason": ended,
                              "n_tokens": len(tokens)}
    stats = eng.stats()
    # one launched row dropped for every stream an EOS cut short
    assert stats["pipeline"]["rows_discarded"] == cut_short
    assert stats["kv_pages_used"] == 0
    assert eng.cache.pools["full"].available == 64
    assert eng.cache.slots.used == 0 and stats["state"]["slots_used"] == 0
    assert stats["running"] == stats["waiting"] == 0


def test_cancel_with_a_program_in_flight_drops_its_row():
    eng = _family_engine("gpt2")
    keep = eng.submit([9, 4], max_tokens=12)
    cut = eng.submit([5, 100, 23, 77], max_tokens=40)
    for _ in range(3):
        eng.step()
    assert len(eng._flights) == 1
    assert cut in [seq for seq, _ in eng._flights[0].rows]
    assert cut.launched == cut.generated + 1 == 4
    eng.cancel(cut.sid)
    eng.step()
    # retired at once, pages and row back, nothing of the launched step
    assert cut.finished and cut.held.slot is None and cut.held.pages == []
    assert cut.generated == 3 and len(cut.tokens) == 4 + 3
    frames = []
    while not cut.out.empty():
        frames.append(cut.out.get())
    assert frames[-1] == {"done": True, "reason": "cancelled",
                          "n_tokens": 3}
    assert [f["index"] for f in frames[:-1]] == [0, 1, 2]
    late = eng.submit([80, 1, 33], max_tokens=5)    # takes the row
    tokens = _step_until_done(eng, [keep, late])
    assert late.finished and eng.stats()["pipeline"]["rows_discarded"] == 1
    fresh = _family_engine("gpt2").start()
    assert tokens == [fresh.generate([9, 4], max_tokens=12),
                      fresh.generate([80, 1, 33], max_tokens=5)]
    fresh.stop()
    assert eng.cache.pools["full"].used == 0 and eng.cache.slots.used == 0


def test_stop_delivers_the_ids_left_unread():
    eng = _family_engine("gpt2")
    seq = eng.submit([9, 4], max_tokens=12)
    eng.step()
    eng.step()
    assert len(eng._flights) == 1 and seq.launched == seq.generated + 1
    eng.stop()
    assert not eng._flights and seq.launched == seq.generated == 3
    assert eng.stats()["pipeline"]["drains"]["stop"] == 1
    s = eng.stats()
    assert sum(s["phase_s"].values()) + s["llm.other"] == \
        pytest.approx(s["step_s"], rel=1e-12)


def test_eviction_with_a_program_in_flight_drains_first():
    """A pool too small for three sequences at their lengths: the pages
    phase finds the pool dry with a program's ids unread, delivers them
    (a victim re-prefills from ALL its tokens) and only then evicts;
    greedy output is what a roomy engine serves."""
    requests = MIXED[:2] + MIXED[4:5]
    roomy = _family_engine("gpt2")
    want = _step_until_done(
        roomy, [roomy.submit(p, max_tokens=n + 8) for p, n in requests])
    tight = _family_engine("gpt2", num_pages=12)
    got = _step_until_done(
        tight, [tight.submit(p, max_tokens=n + 8) for p, n in requests])
    assert got == want
    stats = tight.stats()
    assert stats["evictions"] > 0
    assert stats["pipeline"]["drains"]["evict"] >= stats["evictions"] > 0
    assert stats["pipeline"]["rows_discarded"] == 0
    assert stats["kv_pages_used"] == 0 and tight.cache.slots.used == 0


class _Unreadable:
    """Ids of a program that failed on the device: the launch went
    through, the error waits at the fetch."""

    def __array__(self, *a, **kw):
        raise RuntimeError("injected device failure")


def test_a_device_error_surfaces_at_the_fetch_and_the_loop_lives():
    """Every sequence with anything in flight gets its error frame: one
    still running, and one that left the batch at its last launch and
    whose last ids were unread."""
    eng = _family_engine("gpt2")
    ending = eng.submit([9, 4], max_tokens=3)
    running = eng.submit([5, 100, 23, 77], max_tokens=50)
    eng.step()
    eng.step()
    flight, = eng._flights
    assert ending not in eng._running and not ending.finished
    assert ending in [seq for seq, _ in flight.rows]
    assert ending.generated == 2 and ending.launched == 3
    flight.ids = _Unreadable()
    eng.start()
    try:
        a, b = list(eng.frames(ending)), list(eng.frames(running))
        assert [f.get("index") for f in a[:-1]] == [0, 1]
        for frames in (a, b):
            assert "injected device failure" in frames[-1]["error"]
        stats = eng.stats()
        assert stats["step_errors"] == 1
        assert stats["pipeline"]["drains"]["error"] == 1
        assert stats["kv_pages_used"] == 0 and eng.cache.slots.used == 0
        assert stats["running"] == stats["waiting"] == 0
        assert not eng._flights
        # the loop lives, and serves what a fresh engine serves
        again = eng.generate([80, 1, 33], max_tokens=5)
    finally:
        eng.stop()
    fresh = _family_engine("gpt2").start()
    assert again == fresh.generate([80, 1, 33], max_tokens=5)
    fresh.stop()


def test_sampled_tokens_are_the_samplers_for_the_launched_index(setup):
    """temperature > 0: the key's token index is the LAUNCHED count, so a
    request's draws are what the sampler gives, asked directly, for
    (seed, 0), (seed, 1), ... on the full forward's logits; alone and
    among others; and what the engine's loop gave before the pipeline."""
    from ray_tpu.llm.sampling import jit_sampler, pack_rows, seed_words

    eng, params = setup
    prompt, seed, steps = [10, 20, 30], 977, 10
    model, sampler = GPT2(CFG), jit_sampler(1)[0]
    toks = list(prompt)
    for index in range(steps):
        logits = model.apply(params, jnp.asarray([toks], jnp.int32))
        knobs, words = pack_rows([(SAMPLED, seed_words(seed), index)], 1)
        toks.append(int(np.asarray(
            sampler(logits[:, -1:], knobs, words))[0]))
    want = toks[len(prompt):]
    assert want == [100, 94, 498, 347, 201, 498, 380, 281, 9, 30]
    assert eng.generate(prompt, max_tokens=steps, params=SAMPLED,
                        seed=seed) == want
    others = [eng.submit(p, 12, sp, sd) for p, sp, sd in (
        ([7, 8, 9, 11], SamplingParams(temperature=1.1), 5),
        ([300, 2], SamplingParams(), None))]
    assert _tokens(eng, eng.submit(prompt, steps, SAMPLED, seed)) == want
    for seq in others:
        list(eng.frames(seq))


# ------------------------------------ the ledger of runs, stats()["runs"]

class _Ticks:
    """A wall clock that moves 2**-10 s at every reading (sums of such
    steps are exact in binary: the identities hold to the last bit) and
    further when told to."""

    def __init__(self):
        self.now = 0.0

    def __call__(self):
        self.now += 2.0 ** -10
        return self.now


def _clocked(eng):
    """Put ``eng``'s phases, and so its ledger, on a ``_Ticks``."""
    from ray_tpu.llm.engine import PHASE_LEAVES
    from ray_tpu.util.spans import Phases

    clock = _Ticks()
    eng._phases = Phases(PHASE_LEAVES, "llm.other", lock=eng._lock,
                         cpu_every=16, clock=clock)
    eng._phase = eng._phases.leaf
    return clock


def _ledger_delta(after, before):
    """What stats()["runs"] gained, by program and key."""
    out = {}
    for name, run in after["runs"].items():
        was = before["runs"].get(name, {})
        out[name] = {
            k: [a - b for a, b in zip(v, was.get(k, [0] * len(v)))]
            if isinstance(v, list) else v - was.get(k, 0)
            for k, v in run.items()}
    return out


@pytest.mark.parametrize("family", ["gpt2", "granitemoehybrid"])
def test_the_ledger_of_runs_adds_up(family):
    """Prefills of three buckets and decode steps over three rows, one
    request cancelled with a row in the air: between two instants with
    nothing in flight the programs' intervals, the time that is nobody's
    and the intervals that held a compile tile the clock, and the counts
    are the engine's other counters."""
    eng = _family_engine(family)
    _clocked(eng)
    launches = []
    real_launch = eng._launch
    eng._launch = lambda flight: (launches.append(flight.launched_at),
                                  real_launch(flight))[1]

    def serve():
        seqs = [eng.submit(p, max_tokens=n) for p, n in MIXED]
        for _ in range(4):
            eng.step()
        eng.cancel(seqs[1].sid)         # running, a row of it in flight
        _step_until_done(eng, seqs)
        return eng.stats(), eng._delivered_at

    def total(runs, key, of=lambda name: True):
        return sum(r[key] for name, r in runs.items() if of(name))

    def prefill(name):
        return name.startswith("llm_prefill[")

    cold, d0 = serve()      # every program compiles in this pass
    assert set(cold["runs"]) == {"llm_decode", "llm_prefill[8]",
                                 "llm_prefill[16]", "llm_prefill[32]"}
    assert cold["runs_voided_s"] > 0
    assert total(cold["runs"], "paced_s") + cold["runs_unpaced_s"] \
        + cold["runs_voided_s"] == d0 - launches[0]
    warm, d1 = serve()
    assert warm["compiles"] == cold["compiles"]
    for stats, runs in ((cold, cold["runs"]), (warm, warm["runs"])):
        assert total(runs, "runs", prefill) == stats["prefills"]
        assert runs["llm_decode"]["runs"] == \
            stats["attention"]["decode_runs"]
        assert total(runs, "rows") == stats["tokens_generated"] \
            + stats["pipeline"]["rows_discarded"]
        assert total(runs, "tokens", prefill) == \
            stats["prefill_bucket_tokens"]
    assert warm["pipeline"]["rows_discarded"] == 2
    gained = _ledger_delta(warm, cold)
    assert warm["runs_voided_s"] == cold["runs_voided_s"]
    assert total(gained, "paced_s") + warm["runs_unpaced_s"] \
        - cold["runs_unpaced_s"] == d1 - d0
    for name, run in gained.items():
        assert sum(run["by_ms"]) == run["runs"] > 0, name
        assert sum(run["s_by_ms"]) == run["paced_s"] > 0, name
        if prefill(name):
            assert run["rows"] == run["runs"]
            assert run["tokens"] == run["runs"] * int(name[12:-1])
        else:
            assert run["tokens"] == run["rows"]


class _Slow:
    """Ids that take ``seconds`` of ``clock`` to reach the host."""

    def __init__(self, ids, clock, seconds):
        self.ids, self.clock, self.seconds = ids, clock, seconds

    def __array__(self, *a, **kw):
        self.clock.now += self.seconds
        return np.asarray(self.ids)


def test_a_stalled_fetch_lands_in_its_programs_bucket_and_no_other():
    eng = _family_engine("gpt2")
    clock = _clocked(eng)
    _step_until_done(eng, [eng.submit(p, max_tokens=n)
                           for p, n in MIXED[:2]])
    before = eng.stats()
    seqs = [eng.submit(p, max_tokens=n) for p, n in MIXED[:2]]
    for _ in range(4):
        eng.step()
    flight, = eng._flights
    assert flight.name == "llm_decode"
    flight.ids = _Slow(flight.ids, clock, 2.0)
    _step_until_done(eng, seqs)
    gained = _ledger_delta(eng.stats(), before)
    # 2 s and a few readings: 1,024 to 2,048 ms
    slow = int(2000).bit_length()
    for name, run in gained.items():
        stalled = [int(name == "llm_decode" and i == slow)
                   for i in range(16)]
        assert [n for n in run["by_ms"][8:]] == stalled[8:], name
        assert sum(run["by_ms"]) == run["runs"]
    decode = gained["llm_decode"]
    assert 2.0 <= decode["s_by_ms"][slow] < 2.048
    assert decode["paced_s"] - decode["s_by_ms"][slow] < 0.1 * decode["runs"]


def test_an_interval_that_holds_a_compile_is_in_no_sum(monkeypatch):
    """The first prefill's launch compiles four programs and is in the
    air while the first decode step's compiles its own: neither has an
    interval; the second decode step is the first timed run."""
    import contextlib

    from ray_tpu.llm import engine as engine_mod

    eng = _family_engine("gpt2")
    clock = _clocked(eng)

    @contextlib.contextmanager
    def annotate(name, **tags):
        if name == "llm.compile":
            clock.now += 64.0
        yield

    monkeypatch.setattr(engine_mod, "annotate", annotate)
    _step_until_done(eng, [eng.submit([9, 4], max_tokens=6)])
    stats = eng.stats()
    assert stats["compiles"] == 5
    prefill, decode = (stats["runs"][name]
                       for name in ("llm_prefill[8]", "llm_decode"))
    assert (prefill["runs"], sum(prefill["by_ms"]), prefill["paced_s"]) \
        == (1, 0, 0.0)
    assert (decode["runs"], sum(decode["by_ms"])) == (5, 4)
    assert decode["paced_s"] == sum(decode["s_by_ms"]) < 1.0
    assert stats["runs_voided_s"] >= 5 * 64.0
    # a bucket new to a warm engine: the decode step in the air while it
    # compiles is dropped with it
    first = eng.submit([9, 4], max_tokens=9)
    eng.step()
    eng.step()
    assert [f.name for f in eng._flights] == ["llm_decode"]
    _step_until_done(eng, [first, eng.submit(list(range(1, 12)),
                                             max_tokens=2)])
    again = _ledger_delta(eng.stats(), stats)
    assert again["llm_prefill[16]"]["runs"] == 1
    assert sum(again["llm_prefill[16]"]["by_ms"]) == 0
    assert sum(again["llm_decode"]["by_ms"]) == \
        again["llm_decode"]["runs"] - 1
    assert sum(again["llm_prefill[8]"]["by_ms"]) == 1
    assert sum(r["paced_s"] for r in again.values()) < 1.0


@pytest.mark.parametrize("cause", ["evict", "empty", "stop", "error"])
def test_no_interval_spans_a_drain_or_the_poison_pass(cause):
    """128 s pass right after the pipeline is emptied: they are nobody's
    (``runs_unpaced_s``), and the next program's interval starts at its
    own launch."""
    eng = _family_engine("gpt2", **({"num_pages": 12}
                                    if cause == "evict" else {}))
    clock = _clocked(eng)
    real_drain, real_poison = eng._drain, eng._poison
    jumps = []

    def drain(why):
        emptied = bool(eng._flights)
        real_drain(why)
        if emptied and why == cause:
            clock.now += 128.0
            jumps.append(why)

    def poison(e):
        real_poison(e)
        clock.now += 128.0
        jumps.append("error")

    eng._drain, eng._poison = drain, poison
    requests = MIXED[:2] + MIXED[4:5]
    seqs = [eng.submit(p, max_tokens=n + 8) for p, n in requests]
    if cause in ("stop", "error"):
        eng.step()
        eng.step()
        if cause == "stop":
            eng.stop()
        else:
            eng._flights[0].ids = _Unreadable()
            with pytest.raises(RuntimeError, match="injected") as err:
                eng.step()
            eng._poison(err.value)
            assert all(s.finished for s in seqs) and not eng._flights
            seqs = [eng.submit(p, max_tokens=n) for p, n in requests]
    while not all(s.finished for s in seqs):
        eng.step()
    if cause == "empty":
        _step_until_done(eng, [eng.submit([9, 4], max_tokens=3)])
    stats = eng.stats()
    assert jumps and set(jumps) == {cause}
    assert stats["pipeline"]["drains"][cause] >= len(jumps)
    # (the time after the last drain of all waits for a launch to end it)
    assert stats["runs_unpaced_s"] >= 128.0 * (
        len(jumps) - (cause == "empty")) >= 128.0
    for name, run in stats["runs"].items():
        assert run["by_ms"][12:] == [0] * 4, name
    assert sum(r["paced_s"] for r in stats["runs"].values()) \
        + stats["runs_voided_s"] < 128.0


def test_stats_from_another_thread_mid_step_still_add_up():
    """A program's entry is written in one go under the engine's lock: a
    reading taken while the loop runs holds as many intervals as runs."""
    import sys

    eng = _family_engine("gpt2").start()
    interval = sys.getswitchinterval()
    try:
        for p, n in MIXED:                      # every compile
            eng.generate(p, max_tokens=n)
        before = eng.stats()
        sys.setswitchinterval(1e-5)
        seqs = [eng.submit(p, max_tokens=n + 20) for p, n in MIXED]
        readings = 0
        deadline = time.time() + 120
        while not all(s.finished for s in seqs):
            assert time.time() < deadline
            now = eng.stats()
            for name, run in _ledger_delta(now, before).items():
                assert sum(run["by_ms"]) == run["runs"], name
                assert sum(run["s_by_ms"]) == \
                    pytest.approx(run["paced_s"], abs=1e-9)
                if name != "llm_decode":
                    assert run["rows"] == run["runs"]
                    assert run["tokens"] == run["runs"] * int(name[12:-1])
            readings += 1
        assert readings > 3
    finally:
        sys.setswitchinterval(interval)
        eng.stop()
    assert eng.stats()["runs_voided_s"] == before["runs_voided_s"]


# ------------------------------ the contract of stats() over the caches

# The keys every engine's stats() has, then those a family's layout adds.
STATS_KEYS = {
    "attention", "compiles", "cpu_sample", "device", "evictions",
    "kv_pages_total", "kv_pages_used", "last_error", "llm.other",
    "max_context", "peak_hbm_bytes", "phase_cpu_s", "phase_s", "pipeline",
    "prefill_bucket_tokens", "prefill_tokens", "prefills", "programs",
    "running", "runs", "runs_unpaced_s", "runs_voided_s", "sampling",
    "step_cpu_s", "step_errors", "step_s", "steps", "tokens_generated",
    "tpot_count", "tpot_s_total", "ttft_prefill_s_total", "ttft_requests",
    "ttft_waiting_s_total", "waiting"}
_MOE = {"moe", "moe_prefill"}
FAMILY_KEYS = {
    "gpt2": set(), "llama": set(), "olmoe": _MOE,
    "granitemoehybrid": _MOE | {"state"}, "lfm2moe": _MOE | {"state"},
    "kimik2": _MOE, "kimilinear": _MOE | {"state"},
    "xing40": _MOE | {"residual"}, "olmohybrid": {"state"},
    "cohere2moe": _MOE | {"kv_pages"}}
ATTENTION_KEYS = {"decode_runs", "kv_rows_read", "kv_rows_held",
                  "kv_row_bytes"}
LATENT_KEYS = {"latent_dim", "rope_dim"}
WINDOW_KEYS = {"window", "window_layers", "window_rows_read",
               "window_rows_held", "window_positions_dropped"}
STATE_KEYS = {"slots_total", "slots_used", "decode_runs",
              "state_rows_updated", "state_row_bytes", "mixer_weight_bytes"}

# (b) three requests a pool of 12 pages of 4 holds whole (3 + 2 + 3), the
# third past the tiny window of 8; (c) three that need 22 pages between them.
HELD = (([5, 100, 23, 77, 9], 4), ([9, 4], 6), (list(range(20, 29)), 3))
TIGHT = tuple((p, n + 8) for p, n in MIXED[:2] + MIXED[4:5])


def _gauges(name):
    """The series of one gauge as the registry holds them now, by their
    ``group`` tag (None: the gauge has no tags)."""
    from ray_tpu.util.metrics import registry

    for snap in registry().snapshot():
        if snap["name"] == name:
            return {s["tags"].get("group"): s["value"]
                    for s in snap["series"]}
    raise AssertionError(f"gauge {name} not published")


def test_the_contract_covers_every_family():
    from ray_tpu.models import MODEL_FAMILIES

    assert set(FAMILY_KEYS) == set(MODEL_FAMILIES)


@pytest.mark.parametrize("family", sorted(FAMILY_KEYS))
def test_stats_of_what_a_sequence_holds_by_family(family):
    """What stats() say of the device's caches, for every family's
    layout: (a) the key sets, absent keys included; (b) with a pool that
    holds every request, the closed form of what the decode steps read,
    hold and update; (c) with a pool that forces an eviction, every
    stream whole, the victim's too, and everything given back at the end:
    each group's pages, every slot."""
    from math import ceil, prod

    from ray_tpu.models import MODEL_FAMILIES

    page, pages, rows = 4, 12, 3
    eng = _family_engine(family, num_pages=pages, max_batch=rows)
    cfg = eng.model_cfg
    spec = MODEL_FAMILIES[family].cache(cfg)
    per_seq = ceil(min(cfg.max_seq, pages * page) / page)
    ring = ceil(spec.window / page) if spec.window_layers else 0

    # (a) before any traffic and after it: the same keys
    fresh = eng.stats()
    _step_until_done(eng, [eng.submit(list(p), max_tokens=n)
                           for p, n in HELD])
    stats = eng.stats()
    for s in (fresh, stats):
        optional = FAMILY_KEYS[family] - (_MOE if s is fresh else set())
        assert set(s) == STATS_KEYS | optional
        assert set(s["attention"]) == ATTENTION_KEYS \
            | (LATENT_KEYS if spec.latent_dim else set()) \
            | (WINDOW_KEYS if spec.window_layers else set())
        if "state" in s:
            assert set(s["state"]) == STATE_KEYS
        if "kv_pages" in s:
            assert set(s["kv_pages"]) == {"full", "window"}
            assert all(set(g) == {"used", "total"}
                       for g in s["kv_pages"].values())
    assert ("state" in stats) == bool(spec.state_layers)
    assert ("kv_pages" in stats) == bool(spec.window_layers)

    # (b) a request of p prompt tokens and m tokens out takes part in
    # m - 1 decode steps, at p .. p + m - 2 positions cached
    steps = [(len(p), len(p) + n - 1) for p, n in HELD]
    runs = max(n for _, n in HELD) - 1
    full = sum(ceil((c + 1) / page) for a, b in steps for c in range(a, b))
    kept = sum(ceil(min(c + 1, spec.window) / page)
               for a, b in steps for c in range(a, b)) if ring else 0
    att = stats["attention"]
    assert att["decode_runs"] == runs
    assert att["kv_rows_read"] == \
        page * (full * spec.kv_layers + kept * spec.window_layers)
    assert att["kv_rows_held"] == runs * rows * page * (
        per_seq * spec.kv_layers + ring * spec.window_layers)
    if spec.latent_dim:
        assert (att["latent_dim"], att["rope_dim"]) == \
            (spec.latent_dim, spec.rope_dim)
        assert att["kv_row_bytes"] == spec.row_width * 4    # float32 here
    else:
        assert att["kv_row_bytes"] == 2 * spec.kv_heads * spec.head_dim * 4
    if spec.window_layers:
        assert (att["window"], att["window_layers"]) == \
            (spec.window, spec.window_layers)
        assert att["window_rows_read"] == page * kept * spec.window_layers
        assert att["window_rows_held"] == \
            runs * rows * page * ring * spec.window_layers
        assert att["window_positions_dropped"] == \
            page * (full - kept) * spec.window_layers > 0
        assert stats["kv_pages"] == {
            "full": {"used": 0, "total": pages},
            "window": {"used": 0, "total": rows * ring}}
    assert (stats["kv_pages_used"], stats["kv_pages_total"]) == (0, pages)
    if spec.state_layers:
        assert stats["state"] == {
            "slots_total": rows, "slots_used": 0, "decode_runs": runs,
            "state_rows_updated":
                sum(b - a for a, b in steps) * spec.state_layers,
            "state_row_bytes":
                4 * (prod(spec.conv_shape) if spec.conv_shape else 0)
                + 4 * (prod(spec.ssm_shape) if spec.ssm_shape else 0),
            "mixer_weight_bytes": cfg.mixer_params()
            * np.dtype(cfg.param_dtype).itemsize}
    assert stats["evictions"] == 0

    # (c) the same engine, traffic its pool cannot hold at once
    seqs = [eng.submit(list(p), max_tokens=n) for p, n in TIGHT]
    streams = _step_until_done(eng, seqs)
    after = eng.stats()
    assert after["evictions"] > 0
    for seq, (_, n) in zip(seqs, TIGHT):
        frames = []
        while not seq.out.empty():
            frames.append(seq.out.get())
        assert [f["index"] for f in frames[:-1]] == list(range(n))
        assert frames[-1] == {"done": True, "reason": "length",
                              "n_tokens": n}
    # ... and each stream is what the request gives alone, unevicted
    evicted = after["evictions"]
    alone = [_step_until_done(eng, [eng.submit(list(p), max_tokens=n)])[0]
             for p, n in TIGHT]
    assert streams == alone
    after = eng.stats()
    assert after["evictions"] == evicted
    assert after["running"] == after["waiting"] == 0
    assert after["kv_pages_used"] == 0
    assert _gauges("rt_llm_kv_pages_used")["full"] == 0.0
    assert _gauges("rt_llm_state_slots_used")[None] == 0.0
    if spec.window_layers:
        assert all(g["used"] == 0 for g in after["kv_pages"].values())
        assert _gauges("rt_llm_kv_pages_used")["window"] == 0.0
    if spec.state_layers:
        assert after["state"]["slots_used"] == 0
