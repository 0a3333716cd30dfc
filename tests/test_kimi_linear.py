"""Kimi-Linear (models/kimi_linear.py: Kimi Delta Attention, a gated delta
rule with a decay per key channel whose state is a matrix a head, in the
state pool, beside latent attention with no position encoding in the latent
pool; a dense layer ahead of a shared expert beside routed ones) held to
its plain float32 reference (benchmark/reference/kimi_linear_ref.py) at a
tiny size on the CPU: [kda (dense FFN), kda, kda, mla, kda, mla], 64 wide, 4
KDA heads of 16 (chunks of 8 in blocks of 4), 4 latent heads over rank 24
and widths 16 | 8 | 16, top-2 of 8 experts of width 32.  Through the model,
the chunked scan against the token-by-token recurrence, the engine's jitted
forward with BOTH pools, the engine itself, the expert shares, the loss and
the family registry."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import kimi_linear_ref as ref
from benchmark.tools import kimi_linear_faults as faults
from ray_tpu.models import MODEL_FAMILIES, CacheSpec, family_of
from ray_tpu.models.kimi import KimiK2Config, MLAttention
from ray_tpu.models.kimi_linear import (KimiLinear, KimiLinearConfig,
                                        kda_scan, kda_step,
                                        kimi_linear_init,
                                        kimi_linear_loss_fn)

CFG = KimiLinearConfig.tiny(remat=False)
# The reference's configuration: the source's keys at the tiny size.
CONFIG = {"num_hidden_layers": 6, "hidden_size": 64,
          "linear_attn_config": {
              "kda_layers": [1, 2, 3, 5], "full_attn_layers": [4, 6],
              "num_heads": 4, "head_dim": 16, "short_conv_kernel_size": 4},
          "num_attention_heads": 4, "q_lora_rank": None, "kv_lora_rank": 24,
          "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
          "mla_use_nope": True, "first_k_dense_replace": 1,
          "num_experts": 8, "num_shared_experts": 1,
          "num_experts_per_token": 2, "moe_renormalize": True,
          "moe_router_activation_func": "sigmoid", "num_expert_group": 1,
          "topk_group": 1, "routed_scaling_factor": 2.446,
          "rms_norm_eps": 1e-5}
PROMPTS = [tuple(range(3, 10)), tuple(range(40, 71)), (200, 7, 91, 16)]


def _scaled(params, factor=8.0):
    """std-0.02 weights at 64 wide leave every router near-uniform and
    every softmax flat; scaled up, routing and attention are decided and
    an error of the mathematics shows (tests/test_olmoe.py).  The 1-D
    leaves (norm scales, expert_bias, A_log, dt_bias) and the taps stay as
    drawn."""
    return jax.tree_util.tree_map_with_path(
        lambda path, w: w if w.ndim == 1 or path[-1].key == "conv_w"
        else factor * w, params)


@pytest.fixture(scope="module")
def params():
    return _scaled(kimi_linear_init(CFG, jax.random.PRNGKey(7)))


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 29)),
                       jnp.int32)


# ------------------------------------------------ forward against reference

def test_forward_equals_reference(params, tokens):
    """The full forward (the chunked scan over 29 positions: three whole
    chunks of 8 and a part; the expanded latent attention) against the
    reference's token-by-token recurrence; logits of size ~1; the decay is
    live (some channel forgets half within 3 tokens, some keep 99% a
    token)."""
    want = ref.forward(CONFIG, params, tokens)
    assert float(jnp.std(want)) > 0.05
    got = jax.jit(lambda p, t: KimiLinear(CFG).apply(p, t))(params, tokens)
    np.testing.assert_allclose(got, want, atol=5e-5)
    last = ref.forward(CONFIG, params, tokens, last=5)
    np.testing.assert_allclose(last, want[:, -5:], atol=1e-6)


def test_the_reference_a_layer_a_jit_equals_its_eager_form(params, tokens):
    """``forward(by_layer=True)`` (what the fault tool runs at the timed
    sizes on the chip: each layer under ``jit``, every held expert over
    every row times its weight or 0) is the eager reference within
    float32's rounding (the compiler fuses what eager runs apart); with
    ``lengths``, rows filled behind to one length give the logits that
    end at each row's OWN length."""
    want = ref.forward(CONFIG, params, tokens)
    got = ref.forward(CONFIG, params, tokens, by_layer=True)
    np.testing.assert_allclose(got, want, atol=5e-5)
    filled = tokens.at[1, 20:].set(0)          # row 1 is 20 long
    got = ref.forward(CONFIG, params, filled, last=4, lengths=[29, 20],
                      by_layer=True)
    np.testing.assert_allclose(got[0], want[0, 25:], atol=5e-5)
    np.testing.assert_allclose(got[1], want[1, 16:20], atol=5e-5)


# --------------------------------------- the chunked scan alone

def _recurrence(q, k, v, g, beta, state=None):
    """The reference's recurrence from a given state, returning it."""
    b, t, h, d = q.shape
    s = jnp.zeros((b, h, d, d)) if state is None else state
    out = []
    for i in range(t):
        s = jnp.exp(g[:, i])[..., None] * s
        err = v[:, i] - jnp.einsum("bhkv,bhk->bhv", s, k[:, i])
        s = s + (beta[:, i][..., None] * k[:, i])[..., None] \
            * err[..., None, :]
        out.append(jnp.einsum("bhkv,bhk->bhv", s, q[:, i]))
    return jnp.stack(out, 1), s


def _drawn(t, decay, seed=0, b=2, h=3, d=16):
    rng = np.random.default_rng(seed)
    q, k, v = (jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
               for _ in range(3))
    q, k = (x / jnp.linalg.norm(x, axis=-1, keepdims=True) for x in (q, k))
    g = -jnp.asarray(rng.uniform(0, decay, (b, t, h, d)), jnp.float32)
    beta = jnp.asarray(rng.uniform(size=(b, t, h)), jnp.float32)
    state = jnp.asarray(rng.normal(size=(b, h, d, d)), jnp.float32)
    return (q, k, v, g, beta), state


@pytest.mark.parametrize("c,block", [(8, 8), (12, 4), (24, 8), (48, 16),
                                     (128, 8), (128, 16)])
def test_the_inverse_by_blocks_is_the_inverse(c, block):
    """``(I + A)^-1`` of a strictly lower-triangular ``A`` with entries to 2,
    by blocks: one block, two levels, an odd count of blocks (the last one
    joined a level later), and a chunk of 128 from blocks of 8 and of 16."""
    from ray_tpu.models.kimi_linear import _unit_lower_inverse

    a = np.tril(np.random.default_rng(c + block).uniform(
        -2, 2, (2, 3, c, c)), -1) / np.sqrt(block)
    got = _unit_lower_inverse(jnp.asarray(a, jnp.float32), block)
    want = np.linalg.inv(np.eye(c) + a)
    assert float(np.max(np.abs(np.triu(got, 1)))) == 0.0
    np.testing.assert_allclose(got, want, rtol=2e-5,
                               atol=2e-5 * np.max(np.abs(want)))


@pytest.mark.parametrize("carried", [False, True], ids=["zero", "carried"])
@pytest.mark.parametrize("t,chunk,sub", [
    (1, 16, 4), (3, 16, 4), (16, 16, 4), (32, 16, 4), (45, 16, 4),
    (130, 64, 16), (300, 64, 16), (130, 128, 16), (300, 128, 16),
    (40, 64, 16)])
def test_chunked_scan_equals_the_recurrence(t, chunk, sub, carried):
    """Lengths that are and are not whole chunks of 16 (blocks of 4), one
    shorter than a block; chunks of 64 and 128 in blocks of 16 with betas
    to 2 (the family draws them under 1; the scan is Olmo-Hybrid's too),
    several chunks and a last one part-filled; 40 positions under a chunk
    of 64: one chunk of THREE blocks; from a zero and from a carried
    state."""
    (q, k, v, g, beta), state = _drawn(t, 0.3, seed=t + chunk - 16)
    if chunk > 16:
        beta = 2.0 * beta
    args = (q, k, v, g, beta)
    state = state if carried else None
    want, s_want = _recurrence(*args, state)
    got, s_got = kda_scan(*args, chunk, sub, state)
    atol = 2e-6 if chunk == 16 else 5e-6
    np.testing.assert_allclose(got, want, atol=atol)
    np.testing.assert_allclose(s_got, s_want, atol=atol)
    # (the correction is real: without it the outputs differ)
    plain, _ = faults._uncorrected(None)[0](*args, chunk, sub, state)
    assert t == 1 or float(jnp.max(jnp.abs(plain - want))) > 1e-2


def test_a_chunk_whose_decay_passes_e_minus_100_stays_finite_and_right():
    """``1 / Gamma`` would overflow float32 here: the cumulative log-decay
    of a chunk of 64 reaches -170 on some channels (and -6 a token on
    others would do it inside ONE block of 16: the block against itself
    takes pairwise differences, not a reference point).  The chunked form
    exponentiates differences only: finite, and equal to the recurrence
    within float32's rounding."""
    (q, k, v, g, beta), state = _drawn(100, 3.0, seed=3)
    g = g.at[:, 20:36, 0, :4].set(-6.0)
    total = jnp.min(jnp.cumsum(g[:, :64], axis=1))
    assert float(total) < -100
    with np.errstate(over="ignore"):
        assert not np.isfinite(np.exp(-np.float32(total)))  # 1 / Gamma
    want, s_want = _recurrence(q, k, v, g, beta, state)
    got, s_got = kda_scan(q, k, v, g, beta, 64, 16, state)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(s_got, s_want, atol=1e-5, rtol=1e-5)


def test_a_padded_position_is_the_identity():
    """g = 0 and beta = 0 behind the real positions: the state is the last
    real position's, whatever the padded rows' q, k and v."""
    (q, k, v, g, beta), state = _drawn(13, 0.3, seed=5)
    _, s_want = kda_scan(q[:, :9], k[:, :9], v[:, :9], g[:, :9],
                         beta[:, :9], 8, 4, state)
    g = g.at[:, 9:].set(0.0)
    beta = beta.at[:, 9:].set(0.0)
    o, s_got = kda_scan(q, k, v, g, beta, 8, 4, state)
    np.testing.assert_allclose(s_got, s_want, atol=1e-6)
    assert bool(jnp.all(jnp.isfinite(o)))


def test_the_decode_step_is_the_recurrence_on_the_running_rows_slots():
    """Three rows over a pool of 4 slots of layer 1 of 2: rows 0 and 2
    live in slots 3 and 0, row 1 padded (slot 4, outside); row 2 fresh.
    The live slots hold the recurrence's next state, every other slot and
    layer is untouched, and a padded row changes nothing."""
    (q, k, v, g, beta), _ = _drawn(1, 0.3, seed=9, b=3)
    q, k, v, g, beta = (x[:, 0] for x in (q, k, v, g, beta))
    pool = jnp.asarray(np.random.default_rng(1).normal(
        size=(2, 4, 3, 16, 16)), jnp.float32)
    slots = jnp.asarray([3, 4, 0])
    fresh = jnp.asarray([False, False, True])
    o, new = kda_step(pool, 1, slots, fresh, q, k, v, jnp.exp(g), beta)
    start = jnp.stack([pool[1, 3], pool[1, 0], jnp.zeros_like(pool[1, 0])])
    want, s_want = _recurrence(*(x[jnp.asarray([0, 1, 2])][:, None]
                                 for x in (q, k, v, g, beta)), start)
    np.testing.assert_allclose(o[jnp.asarray([0, 2])],
                               want[jnp.asarray([0, 2]), 0], atol=1e-6)
    np.testing.assert_allclose(new[1, 3], s_want[0], atol=1e-6)
    np.testing.assert_allclose(new[1, 0], s_want[2], atol=1e-6)
    np.testing.assert_array_equal(new[0], pool[0])
    np.testing.assert_array_equal(new[1, 1:3], pool[1, 1:3])


# (slots, fresh, slots in the pool, heads, heads a block)
KERNEL_CASES = {
    "full_batch": ([0, 1, 2, 3], [0, 0, 0, 0], 4, 4, 2),
    "padded_rows_among_live": ([2, 6, 0, 6, 4], [0, 0, 0, 0, 0], 6, 4, 2),
    "padded_first_and_last": ([5, 1, 3, 5], [0, 0, 0, 0], 5, 4, 2),
    "fresh_rows": ([1, 0, 3, 2], [1, 0, 0, 1], 4, 4, 2),
    "fresh_beside_padded": ([4, 4, 2, 0], [1, 0, 1, 0], 4, 4, 4),
    "rows_out_of_slot_order": ([3, 0, 2, 1], [0, 0, 0, 0], 4, 4, 1),
    "more_slots_than_rows": ([5, 1], [0, 0], 7, 4, 2),
    "an_odd_number_of_blocks": ([2, 4, 0], [0, 1, 0], 4, 3, 1),
    "every_row_padded": ([3, 3], [0, 0], 3, 4, 2),
}


@pytest.mark.parametrize("case", KERNEL_CASES)
def test_the_step_kernel_is_the_recurrence_and_touches_the_running_rows_alone(
        case):
    """``ops/delta_rule.py kda_step`` (interpreted) on layer 1 of a pool
    of 3 layers against the token-by-token recurrence: a live row's ``o``
    and its slot's new state to 1e-6, a padded row's ``o`` zeros, and every
    slot no row names and every OTHER layer BIT-EQUAL to what it held; the
    by-slot ``jnp`` form gives the same."""
    from ray_tpu.ops import delta_rule

    slots, fresh, n_slots, h, block = KERNEL_CASES[case]
    d = 128
    (q, k, v, g, beta), _ = _drawn(1, 0.3, seed=3, b=len(slots), h=h, d=d)
    q, k, v, g, beta = (x[:, 0] for x in (q, k, v, g, beta))
    q = q * d ** -0.5               # as the mixer scales it
    pool = jnp.asarray(np.random.default_rng(2).normal(
        size=(3, n_slots, h, d, d)), jnp.float32)
    assert delta_rule.supported(pool, q)
    args = (pool, 1, jnp.asarray(slots), jnp.asarray(fresh, bool), q, k, v,
            jnp.exp(g), beta)
    o, new = delta_rule.kda_step(*args, block_heads=block, interpret=True)
    o_slab, new_slab = kda_step(*args)
    live = [i for i, s in enumerate(slots) if s < n_slots]
    for i, s in enumerate(slots):
        if i not in live:
            np.testing.assert_array_equal(o[i], 0.0)
            continue
        start = jnp.zeros_like(pool[1, s]) if fresh[i] else pool[1, s]
        want, s_want = _recurrence(*(x[i:i + 1, None]
                                     for x in (q, k, v, g, beta)),
                                   start[None])
        np.testing.assert_allclose(o[i], want[0, 0], atol=1e-6)
        np.testing.assert_allclose(new[1, s], s_want[0], atol=1e-6)
    idle = [s for s in range(n_slots) if s not in slots]
    np.testing.assert_array_equal(new[1, idle], pool[1, idle])
    np.testing.assert_array_equal(new[jnp.asarray([0, 2])],
                                  pool[jnp.asarray([0, 2])])
    np.testing.assert_allclose(o, o_slab, atol=1e-6)
    np.testing.assert_allclose(new, new_slab, atol=1e-6)


def test_the_step_kernel_is_for_float32_pools_of_whole_tiles():
    """``supported`` reads the pool's shape and dtype alone: the tiny
    preset's 16 x 16 states and a bfloat16 pool stay on the ``jnp`` form."""
    from ray_tpu.ops.delta_rule import supported

    q = jax.ShapeDtypeStruct((2, 4, 128), jnp.float32)

    def pool(dtype, *state):
        return jax.ShapeDtypeStruct((2, 4, 4) + state, dtype)

    assert supported(pool(jnp.float32, 128, 128), q)
    assert supported(pool(jnp.float32, 256, 128), q)
    assert not supported(pool(jnp.float32, 16, 16), q)
    assert not supported(pool(jnp.float32, 128, 64), q)
    assert not supported(pool(jnp.bfloat16, 128, 128), q)


# ------------------------------------------- through the engine's programs

def _against_reference(params, prompts, served, logits, n):
    for prompt, toks, rows in zip(prompts, served, logits):
        want = np.asarray(ref.forward(
            CONFIG, params, jnp.asarray([list(prompt) + toks[:-1]],
                                        jnp.int32)))[0][len(prompt) - 1:]
        assert len(want) == len(rows) == n
        np.testing.assert_allclose(np.stack(rows), want, atol=1e-4)


def test_prefill_then_decode_equals_reference_through_both_pools(params):
    """Three sequences of unequal length, each prefilled padded to its
    bucket (7 -> 8, 31 -> 32, 4 -> 8 positions: the chunked scan over one
    and four chunks of 8, the padding behind the real positions, the state
    and the window stored at the prompt's length into slots that held
    other numbers; the expanded latent attention storing rows into pages
    that held other numbers), then decoded together in a batch of 6 rows
    of which row 1 and row 5 are empty (the recurrence once a row over its
    slot; the absorbed attention over pages of 4 positions), 10 tokens: the
    31-token prompt crosses a chunk, a bucket and several page boundaries
    while it decodes.  At every generated position the logits equal the
    reference's full forward over prompt + generated tokens.  A larger
    batch with more padding gives the same."""
    served, logits = faults.serve(CFG, params, PROMPTS, 10, max_batch=6,
                                  page=4)
    _against_reference(params, PROMPTS, served, logits, 10)
    served9, logits9 = faults.serve(CFG, params, PROMPTS, 10, max_batch=9,
                                    page=4)
    assert served9 == served
    for a, b in zip(logits, logits9):
        np.testing.assert_allclose(np.stack(a), np.stack(b), atol=1e-6)


def test_a_padded_prefill_leaves_the_state_and_the_window_of_its_length(
        params):
    """The same 7-token prompt through the 8 bucket and, padded further,
    through a 16 bucket: the slot's window and state are the same, and
    the other slot is untouched."""
    from ray_tpu.llm.engine import jit_forward
    from ray_tpu.llm.kv_cache import init_pool, init_state

    spec = MODEL_FAMILIES["kimilinear"].cache(CFG)
    fwd = jit_forward(KimiLinear(CFG))
    held = []
    for pad in (8, 16):
        (pages,) = init_pool(spec, 8, 4, CFG.dtype).values()
        state = {k: v + 1 for k, v in init_state(spec, 2,
                                                 CFG.dtype).items()}
        toks = np.zeros((1, pad), np.int32)
        toks[0, :7] = PROMPTS[0]
        pos = np.full((1, pad), -1, np.int32)
        pos[0, :7] = np.arange(7)
        _, _, conv, ssm, *_ = fwd(
            params, toks, pages, np.arange(4, dtype=np.int32)[None], pos,
            state["conv"], state["ssm"], np.array([1], np.int32))
        np.testing.assert_array_equal(conv[:, 0], 1)
        np.testing.assert_array_equal(ssm[:, 0], 1)
        held.append((np.asarray(conv[:, 1]), np.asarray(ssm[:, 1])))
    np.testing.assert_allclose(held[0][0], held[1][0], atol=1e-6)
    np.testing.assert_allclose(held[0][1], held[1][1], atol=1e-6)
    assert float(np.std(held[0][1])) > 1e-3


def _engine(params, **engine):
    from ray_tpu.llm.engine import EngineConfig, GenerationEngine

    return GenerationEngine(
        model_cfg=CFG, params=params,
        engine_cfg=EngineConfig(**{**dict(page_size=4, num_pages=64,
                                          max_batch=2), **engine}))


def _run(engine, *requests):
    seqs = [engine.submit(list(p), max_tokens=n) for p, n in requests]
    while not all(s.finished for s in seqs):
        engine.step()
    assert engine.stats()["step_errors"] == 0, engine.stats()["last_error"]
    return [s.tokens[s.prompt_len:] for s in seqs]


def test_a_slot_that_changes_hands_and_an_eviction_reproduce_the_stream(
        params):
    """Slots change hands without being cleared: the second sequence in
    slot 0 gets the tokens a fresh engine gives it (and those the jitted
    forward serves: the engine's stream is the reference's); an eviction's
    re-prefill rebuilds state, window and latent rows and reproduces the
    stream."""
    engine = _engine(params)
    first = _run(engine, (PROMPTS[1], 9))
    assert engine.stats()["state"]["slots_used"] == 0
    again = _run(engine, (PROMPTS[0], 9), (PROMPTS[2], 9))
    assert again == _run(_engine(params), (PROMPTS[0], 9), (PROMPTS[2], 9))
    assert first == _run(_engine(params), (PROMPTS[1], 9))
    served, _ = faults.serve(CFG, params, [PROMPTS[0], PROMPTS[2]], 9,
                             page=4)
    assert again == served
    requests = ((PROMPTS[0], 20), (PROMPTS[2], 20))
    tight = _engine(params, num_pages=10)
    out = _run(tight, *requests)
    assert tight.stats()["evictions"] > 0
    assert out == _run(_engine(params), *requests)


def test_engine_holds_both_pools_and_counts_what_each_moved(params):
    """ONE cache spec with latent pages AND a state slot: the engine builds
    the latent pool for the 2 latent layers and ``conv`` + ``ssm`` for the
    4 KDA layers, and stats() carries ["attention"] (a latent row) and
    ["state"] (a window and a float32 matrix a head) together."""
    engine = _engine(params, max_batch=4)
    assert list(engine.cache.paged) == ["latent_pages"]
    assert engine.cache.paged["latent_pages"].shape == (2, 64, 4, 128)
    assert set(engine.cache.state) == {"conv", "ssm"}
    assert engine.cache.state["conv"].shape == (4, 4, 3, 192)
    assert engine.cache.state["ssm"].shape == (4, 4, 4, 16, 16)
    assert engine.cache.state["ssm"].dtype == jnp.float32
    _run(engine, (PROMPTS[0], 5), (PROMPTS[1], 5))
    stats = engine.stats()
    state, moe, att = stats["state"], stats["moe"], stats["attention"]
    runs = state["decode_runs"]
    assert runs == att["decode_runs"] == 4          # 5 tokens: 1 + 4 steps
    assert state["state_rows_updated"] == 2 * 4 * runs  # 4 KDA layers
    assert state["state_row_bytes"] == (3 * 192 + 4 * 16 * 16) * 4
    assert state["mixer_weight_bytes"] == CFG.mixer_params() * 4 \
        == (4 * 64 * 64 + 2 * (64 * 8 + 8 * 64) + 64 * 4 + 4 * 192) * 4
    assert att["kv_row_bytes"] == 128 * 4           # float32 here
    assert (att["latent_dim"], att["rope_dim"]) == (24, 8)
    want = 2 * sum(-(-(n + i) // 4) * 4             # 2 latent layers
                   for n in (len(PROMPTS[0]), len(PROMPTS[1]))
                   for i in range(1, 5))
    assert att["kv_rows_read"] == want
    assert moe["layer_runs"] == 5 * runs            # layers WITH experts


def test_the_lowered_forward_names_the_scopes_the_readers_file_by():
    """benchmark/harness/kda_phases.py and mla_phases.py file a trace's
    operations by these names: a decode step has ``kda.step`` and no
    ``kda.scan``, a prefill the other way round; a KDA layer's output
    projection is ``kda.out_proj`` and NOT ``attn.out`` (which belongs to
    the 2 latent layers alone: ``mla.proj_ms.sat`` sums it)."""
    from ray_tpu.llm.engine import jit_forward
    from ray_tpu.llm.kv_cache import init_pool, init_state, pages_for

    spec = MODEL_FAMILIES["kimilinear"].cache(CFG)
    params = jax.eval_shape(
        lambda: kimi_linear_init(CFG, jax.random.PRNGKey(0)))
    kv = jax.eval_shape(lambda: init_pool(spec, 16, 4, CFG.dtype))
    state = jax.eval_shape(lambda: init_state(spec, 2, CFG.dtype))

    def lowered(shape):
        ints = jax.ShapeDtypeStruct(shape, jnp.int32)
        return jit_forward(KimiLinear(CFG)).lower(
            params, ints, kv["latent_pages"], jax.ShapeDtypeStruct(
                (shape[0], pages_for(CFG.max_seq, 4)), jnp.int32),
            ints, state["conv"], state["ssm"],
            jax.ShapeDtypeStruct(shape[:1], jnp.int32)
        ).as_text(debug_info=True)

    decode, prefill = lowered((2, 1)), lowered((1, 16))
    both = ("kda.proj", "kda.conv", "kda.gate", "kda.out_norm",
            "kda.out_proj", "mla.q", "mla.kv", "kv.store", "attn.out",
            "mlp/mlp.dense", "moe.shared", "moe.route", "moe.experts",
            "lm_head")
    for name in both + ("kda.step", "mla.absorb", "kv.attend"):
        assert name in decode, name
    for name in both + ("kda.scan", "mla.expand"):
        assert name in prefill, name
    assert "kda.scan" not in decode and "kda.step" not in prefill
    assert "stablehlo.sine" not in decode + prefill           # no rope
    for text in (decode, prefill):
        outs = [x for x in text.splitlines() if "attn.out" in x]
        assert outs and not any("/kda/" in x for x in outs)
        assert any("kda.conv" in x and "scatter" in x
                   for x in text.splitlines())
    assert any("kda.step" in x and "scatter" in x
               for x in decode.splitlines())
    # the chunked scan is matmuls and one loop: no triangular solve
    assert "triangular_solve" not in prefill + decode
    assert "stablehlo.while" in prefill
    assert "tpu_custom_call" not in decode      # 16 x 16 states: ``jnp``


def test_the_lowered_decode_step_holds_the_kernel_the_reader_files_by_name():
    """benchmark/harness/kda_phases.py files an instruction whose name
    starts with ``kda_step`` under ``kda.step`` (a kernel carries no scope
    path): with states of 128 x 128, lowered for the ``tpu`` platform as
    that backend dispatches, the decode forward calls one such custom call
    once a KDA layer, the pool aliased in and out, and its prefill none."""
    import ray_tpu.models.kimi_linear as kimi_linear
    from ray_tpu.llm.engine import jit_forward
    from ray_tpu.llm.kv_cache import init_pool, init_state, pages_for
    from ray_tpu.ops import delta_rule

    cfg = dataclasses.replace(CFG, kda_head_dim=128)
    spec = MODEL_FAMILIES["kimilinear"].cache(cfg)
    params = jax.eval_shape(
        lambda: kimi_linear_init(cfg, jax.random.PRNGKey(0)))
    kv = jax.eval_shape(lambda: init_pool(spec, 16, 4, cfg.dtype))
    state = jax.eval_shape(lambda: init_state(spec, 2, cfg.dtype))

    def lowered(shape):
        ints = jax.ShapeDtypeStruct(shape, jnp.int32)
        return jit_forward(KimiLinear(cfg)).trace(
            params, ints, kv["latent_pages"], jax.ShapeDtypeStruct(
                (shape[0], pages_for(cfg.max_seq, 4)), jnp.int32),
            ints, state["conv"], state["ssm"],
            jax.ShapeDtypeStruct(shape[:1], jnp.int32)
        ).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)

    with pytest.MonkeyPatch.context() as patch:    # as on the tpu backend
        patch.setattr(kimi_linear, "_step_kernel", delta_rule.supported)
        patch.setattr(delta_rule, "kda_step", functools.partial(
            delta_rule.kda_step, interpret=False))
        decode, prefill = lowered((2, 1)), lowered((1, 16))
    # (the layer is an operand: ONE lowered function, called a KDA layer)
    call, = [x for x in decode.splitlines()
             if "stablehlo.custom_call @tpu_custom_call" in x]
    assert 'kernel_name = "kda_step"' in call
    assert "output_operand_alias" in call and "operand_index = 5" in call
    assert len([x for x in decode.splitlines()
                if "call @_kda_step(" in x]) == cfg.layers_of("kda") == 4
    assert any("kda.step/jit(_kda_step)" in x for x in decode.splitlines())
    assert "kda.step" in decode and "scatter" not in "".join(
        x for x in decode.splitlines() if "kda.step" in x)
    assert "tpu_custom_call" not in prefill and "kda.scan" in prefill


# ------------------------------------------- the latent layer's two options

def test_one_mlattention_serves_both_families():
    """``models/kimi.py MLAttention`` with ``q_lora_rank=None`` (one ``wq``,
    no ``wq_a`` / norm / ``wq_b``) and ``mla_use_nope`` (nothing rotated,
    scale ``(d_n + d_r) ** -0.5``) against the reference's latent
    attention on the same weights; the defaults keep Kimi-K2's leaves."""
    u = jnp.asarray(np.random.default_rng(2).normal(size=(2, 19, 64)),
                    jnp.float32)
    attn = MLAttention(CFG)
    p = attn.init(jax.random.PRNGKey(1), u)
    assert set(p["params"]) == {"wq", "wkv_a", "kv_norm", "wkv_b", "wo"}
    p = _scaled(p, 4.0)
    got, pages = attn.apply(p, u)
    want = ref._mla(u, p["params"], CONFIG, 8)
    assert pages is None and float(jnp.std(want)) > 0.05
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert CFG.softmax_scale == 24 ** -0.5
    k2 = KimiK2Config.tiny()
    assert k2.q_lora_rank == 32 and not k2.mla_use_nope
    leaves = MLAttention(k2).init(jax.random.PRNGKey(1), u)["params"]
    assert set(leaves) == {"wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm",
                           "wkv_b", "wo"}
    assert dataclasses.replace(k2, mla_use_nope=True).softmax_scale \
        == 24 ** -0.5 < k2.softmax_scale
    with pytest.raises(ValueError):
        KimiLinearConfig.tiny(mla_use_nope=False)


# --------------------------------------------------- the share of the experts

def test_all_the_expert_shares_and_the_shared_expert_add_up(params):
    """Expert parallelism over four chips of two experts each (the
    benchmark's cut is 16 chips of 16 of 256), on one layer's input: the
    routed parts the four shares compute plus the shared expert counted
    ONCE equal the uncut reference's whole FFN; and the reference given a
    share computes that share."""
    import flax.linen as nn

    from ray_tpu.models.kimi import ROUTE_NORM_EPS
    from ray_tpu.ops.moe import MoEMLP

    layer = params["params"]["layer_2"]
    h = jnp.asarray(np.random.default_rng(5).normal(size=(1, 23, 64)),
                    jnp.float32)
    flat = h.reshape(23, 64)
    whole = ref.k2_config(CONFIG)
    parts = []
    for rank in range(4):
        moe = dict(layer["moe"])
        for name in ("w_gate", "w_up", "w_down"):
            moe[name] = moe[name][2 * rank:2 * rank + 2]
        op = MoEMLP(d_model=64, d_ff=32, num_experts=8, top_k=2, gated=True,
                    norm_topk_prob=True, scoring="sigmoid", select_bias=True,
                    norm_eps=ROUTE_NORM_EPS, routed_scaling_factor=2.446,
                    act=nn.silu, dtype=jnp.float32, first_expert=2 * rank,
                    held_experts=2)
        y, sown = op.apply({"params": moe}, h, mutable=["intermediates"])
        (m,) = sown["intermediates"]["moe"]
        parts.append((y, int(jnp.sum(m["load"]))))
        share = ref.k2_config(dict(CONFIG, num_experts=2,
                                   first_expert=2 * rank))
        np.testing.assert_allclose(
            y.reshape(23, 64), ref._experts_eager(flat, moe, share),
            atol=2e-5)
    assert sum(n for _, n in parts) == 23 * 2       # every pair, once
    assert all(float(jnp.max(jnp.abs(y))) > 0 for y, _ in parts)
    shared = ref._swiglu(flat, *(layer[k]["kernel"] for k in (
        "shared_gate", "shared_up", "shared_down")))
    want = ref._experts_eager(flat, layer["moe"], whole) + shared
    got = sum(y for y, _ in parts).reshape(23, 64) + shared
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert float(jnp.std(want - shared)) > 1e-2     # the routed part is live


def test_the_model_given_a_share_equals_the_reference_given_it(params,
                                                               tokens):
    """The whole forward with experts 2-5 of 8 held (``first_expert`` /
    ``held_experts``) against the reference told the same share."""
    cfg = dataclasses.replace(CFG, first_expert=2, held_experts=4)
    held = jax.tree_util.tree_map_with_path(
        lambda path, w: w[2:6] if w.ndim == 3 else w, params)
    got = KimiLinear(cfg).apply(held, tokens)
    want = ref.forward(dict(CONFIG, num_experts=4, first_expert=2,
                            published={"num_experts": 8}), held, tokens)
    np.testing.assert_allclose(got, want, atol=5e-5)
    whole = ref.forward(CONFIG, params, tokens)
    assert float(jnp.max(jnp.abs(whole - want))) > 1e-2


# -------------------------------------------------------------- training

def test_loss_and_every_gradient_leaf_equal_the_reference(params, tokens):
    """Through the chunked scan, its inverse by blocks and its pass over
    the chunks, against the reference's gradients through the
    token-by-token recurrence; and ``expert_bias`` takes no gradient."""
    loss, grads = jax.jit(lambda p: jax.value_and_grad(
        lambda q: kimi_linear_loss_fn(CFG, q, {"tokens": tokens}))(p))(
            params)
    want, want_grads = jax.jit(
        lambda p: ref.loss_and_grads(CONFIG, p, tokens))(params)
    assert abs(float(loss) - float(want)) < 1e-5
    flat = jax.tree_util.tree_leaves_with_path(grads)
    assert len(flat) == len(jax.tree_util.tree_leaves(params)) > 100
    frozen = 0
    for (path, g), r in zip(flat, jax.tree_util.tree_leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(r)))
        if path[-1].key == "expert_bias":
            assert scale == 0 and float(jnp.max(jnp.abs(g))) == 0
            frozen += 1
            continue
        assert scale > 0, jax.tree_util.keystr(path)
        assert float(jnp.max(jnp.abs(g - r))) < 5e-4 * scale, \
            jax.tree_util.keystr(path)
    assert frozen == 5


# -------------------------------------------------------------- registry

def test_the_registry_builds_the_seventh_family():
    row = MODEL_FAMILIES["kimilinear"]
    assert len(MODEL_FAMILIES) == 10 and row.config is KimiLinearConfig
    assert family_of(row.tiny()).module is KimiLinear
    full = KimiLinearConfig()               # as published
    assert [i + 1 for i, kind in enumerate(full.layer_types)
            if kind == "mla"] == [4, 8, 12, 16, 20, 24, 27]
    spec = row.cache(full)
    assert spec == CacheSpec(7, 0, 0, 20, (3, 12288), (32, 128, 128),
                             latent_dim=512, rope_dim=64)
    assert spec.row_width == 640
    assert full.mixer_params() == 39_510_016
    assert full.attention_params() == 29_114_368
    cut = dataclasses.replace(full, held_experts=16)
    params = jax.eval_shape(lambda: row.init(cut, jax.random.PRNGKey(0)))
    n = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert abs(n - 4.957e9) < 0.001e9
    from ray_tpu.train.distributed import rules_for_model

    assert rules_for_model("kimi_linear") == row.partition_rules()


def test_a_spec_with_latent_pages_and_a_state_slot_builds_both_pools():
    """The combination ``tests/test_kimi.py``'s ``init_pool`` case and
    ``tests/test_lfm2.py``'s ``init_state`` case each have half of: the
    names ``jit_forward`` carries, in its order, and what it donates."""
    from ray_tpu.llm.engine import jit_forward
    from ray_tpu.llm.kv_cache import (init_pool, init_state, pool_arrays,
                                      state_arrays)

    spec = MODEL_FAMILIES["kimilinear"].cache(CFG)
    assert pool_arrays(spec) == ("latent_pages",)
    assert state_arrays(spec) == ("conv", "ssm")
    kv = init_pool(spec, 8, 4, jnp.bfloat16)
    state = init_state(spec, 2, jnp.bfloat16)
    assert kv["latent_pages"].shape == (2, 8, 4, 128)
    assert state["conv"].shape == (4, 2, 3, 192)
    assert state["conv"].dtype == jnp.bfloat16
    assert state["ssm"].shape == (4, 2, 4, 16, 16)
    assert state["ssm"].dtype == jnp.float32
    params = jax.eval_shape(
        lambda: kimi_linear_init(CFG, jax.random.PRNGKey(0)))
    ints = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    lowered = jit_forward(KimiLinear(CFG)).lower(
        params, ints, kv["latent_pages"],
        jax.ShapeDtypeStruct((2, 4), jnp.int32), ints,
        state["conv"].astype(CFG.dtype), state["ssm"],
        jax.ShapeDtypeStruct((2,), jnp.int32))
    donated = [i for i, d in enumerate(
        jax.tree_util.tree_leaves(lowered.args_info)) if d.donated]
    n_leaves = len(jax.tree_util.tree_leaves(params))
    # after the weights: tokens, PAGES, table, positions, CONV, SSM, slots
    assert donated == [n_leaves + 1, n_leaves + 4, n_leaves + 5]
    out = jax.eval_shape(
        jit_forward(KimiLinear(CFG)), params, ints,
        kv["latent_pages"].astype(CFG.dtype),
        jax.ShapeDtypeStruct((2, 4), jnp.int32), ints,
        state["conv"].astype(CFG.dtype), state["ssm"],
        jax.ShapeDtypeStruct((2,), jnp.int32))
    assert [o.shape for o in out[1:4]] == [
        kv["latent_pages"].shape, state["conv"].shape, state["ssm"].shape]
    assert out[4].shape == (5, 4)           # the experts' counters


# ----------------------------------- the comparison can tell right from wrong

@pytest.fixture(scope="module")
def served_right(params):
    prompts = [list(PROMPTS[1]), list(PROMPTS[0])]
    served, logits = faults.serve(CFG, params, prompts, 12, page=4)
    return prompts, served, logits


@pytest.mark.parametrize("name", faults.FAULTS)
def test_each_fault_moves_the_served_logits(params, served_right, name):
    """The things the chip run holds to the cell's tolerance
    (benchmark/tools/kimi_linear_faults.py), here at the tiny size in
    float32, fed the right program's tokens: each moves some logit by far
    more than the ~1e-5 that separate the right program from the
    reference."""
    prompts, served, right = served_right
    with faults.fault(name, CFG, params) as (cfg, p, how):
        _, wrong = faults.serve(cfg, p, prompts, 12, page=4, forced=served,
                                **how)
    apart = max(float(np.max(np.abs(np.stack(a) - np.stack(b))))
                for a, b in zip(right, wrong))
    assert apart > 1e-3, (name, apart)
