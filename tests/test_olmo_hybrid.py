"""Olmo-Hybrid (models/olmo_hybrid.py: Gated DeltaNet, a gated delta rule
with ONE decay a head, betas up to 2 and a rectangular ``d_k x d_v`` state a
head, in the state pool, beside full multi-head attention with a QK-norm
and no position encoding in the K/V pool; every FFN dense; each norm on its
sublayer's output) held to its plain float32 reference
(benchmark/reference/olmo_hybrid_ref.py) at a tiny size on the CPU: two
periods of three and one, 60 wide, 6 GDN heads of 12 x 24 (neither a power
of two; no block of 16 divides the heads), chunks of 8, 6 attention heads
of 10.  Through the model, the chunked scan against the token-by-token
recurrence, the step kernel interpreted against the by-slot form, the
engine's jitted forward with BOTH pools, the engine itself, each fault of
benchmark/tools/olmo_hybrid_faults.py, the loss and the family registry."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import olmo_hybrid_ref as ref
from benchmark.tools import olmo_hybrid_faults as faults
from ray_tpu.models import MODEL_FAMILIES, CacheSpec, family_of
from ray_tpu.models.kimi_linear import kda_scan, kda_step
from ray_tpu.models.olmo_hybrid import (ATTENTION, GDN, OlmoHybrid,
                                        OlmoHybridConfig, olmo_hybrid_init,
                                        olmo_hybrid_loss_fn)

CFG = OlmoHybridConfig.tiny(remat=False)
# The reference's configuration: the source's keys at the tiny size.
CONFIG = {"model_type": "olmo_hybrid", "num_hidden_layers": 8,
          "hidden_size": 60, "layer_types": list(CFG.layer_types),
          "num_attention_heads": 6, "num_key_value_heads": 6,
          "hidden_act": "silu", "attention_bias": False,
          "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
          "linear_num_key_heads": 6, "linear_num_value_heads": 6,
          "linear_key_head_dim": 12, "linear_value_head_dim": 24,
          "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
          "rope_parameters": {"rope_theta": None}}
PROMPTS = [tuple(range(3, 10)), tuple(range(40, 71)), (200, 7, 91, 16)]


def _scaled(params, factor=4.0):
    """std-0.02 weights at 60 wide leave every softmax flat and every gate
    near its middle; scaled up, attention and the gates are decided and an
    error of the mathematics shows (tests/test_kimi_linear.py).  The 1-D
    leaves (norm scales, A_log, dt_bias) and the taps stay as drawn."""
    return jax.tree_util.tree_map_with_path(
        lambda path, w: w if w.ndim == 1 or path[-1].key == "conv_w"
        else factor * w, params)


@pytest.fixture(scope="module")
def params():
    return _scaled(olmo_hybrid_init(CFG, jax.random.PRNGKey(7)))


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 29)),
                       jnp.int32)


# ------------------------------------------------ forward against reference

def test_forward_equals_reference(params, tokens):
    """The full forward (the chunked scan over 29 positions: three whole
    chunks of 8 and a part; dense attention with the QK-norm and no
    rotation; every norm on its sublayer's output) against the reference's
    token-by-token recurrence; logits of size ~1; some head forgets half
    within a few tokens, and some beta exceeds 1."""
    want = ref.forward(CONFIG, params, tokens)
    assert float(jnp.std(want)) > 0.3
    got = jax.jit(lambda p, t: OlmoHybrid(CFG).apply(p, t))(params, tokens)
    np.testing.assert_allclose(got, want, atol=1e-4)
    last = ref.forward(CONFIG, params, tokens, last=5)
    np.testing.assert_allclose(last, want[:, -5:], atol=1e-6)


def test_the_reference_a_layer_a_jit_equals_its_eager_form(params, tokens):
    """``forward(by_layer=True)`` (what the fault tool runs at the timed
    sizes on the chip) is the eager reference within float32's rounding;
    with ``lengths``, rows filled behind to one length give the logits
    that end at each row's OWN length."""
    want = ref.forward(CONFIG, params, tokens)
    got = ref.forward(CONFIG, params, tokens, by_layer=True)
    np.testing.assert_allclose(got, want, atol=1e-4)
    filled = tokens.at[1, 20:].set(0)          # row 1 is 20 long
    got = ref.forward(CONFIG, params, filled, last=4, lengths=[29, 20],
                      by_layer=True)
    np.testing.assert_allclose(got[0], want[0, 25:], atol=1e-4)
    np.testing.assert_allclose(got[1], want[1, 16:20], atol=1e-4)


def test_loss_and_gradients_equal_the_references(params, tokens):
    """The trainer's loss (remat on, as the trainer runs it) and its
    gradients against the reference's, leaf by leaf, relative to the
    leaf's largest gradient."""
    cfg = dataclasses.replace(CFG, remat=True)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: olmo_hybrid_loss_fn(cfg, p, {"tokens": tokens})))(params)
    want, want_grads = jax.jit(
        lambda p: ref.loss_and_grads(CONFIG, p, tokens))(params)
    assert abs(float(loss) - float(want)) < 1e-4
    for (path, g), w in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree_util.tree_leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(w))) + 1e-8
        assert float(jnp.max(jnp.abs(g - w))) / scale < 1e-3, path
    assert float(jnp.max(jnp.abs(
        want_grads["params"]["layer_0"]["gdn"]["A_log"]))) > 0


# --------------------------------------- the chunked scan alone

def _recurrence(q, k, v, g, beta, state=None):
    """The reference's recurrence from a given state, returning it: g [B,
    T, H, 1] (one decay a head) or [B, T, H, d_k]."""
    b, t, h, dk = q.shape
    s = jnp.zeros((b, h, dk, v.shape[-1])) if state is None else state
    out = []
    for i in range(t):
        s = jnp.exp(g[:, i])[..., None] * s
        err = v[:, i] - jnp.einsum("bhkv,bhk->bhv", s, k[:, i])
        s = s + (beta[:, i][..., None] * k[:, i])[..., None] \
            * err[..., None, :]
        out.append(jnp.einsum("bhkv,bhk->bhv", s, q[:, i]))
    return jnp.stack(out, 1), s


def _drawn(t, decay, seed=0, b=2, h=3, dk=12, dv=24):
    """q and k normalised, ONE log-decay a head in (-decay, 0), beta in
    (0, 2)."""
    rng = np.random.default_rng(seed)
    q, k = (jnp.asarray(rng.normal(size=(b, t, h, dk)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(b, t, h, dv)), jnp.float32)
    q, k = (x / jnp.linalg.norm(x, axis=-1, keepdims=True) for x in (q, k))
    g = -jnp.asarray(rng.uniform(0, decay, (b, t, h, 1)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 2, size=(b, t, h)), jnp.float32)
    state = jnp.asarray(rng.normal(size=(b, h, dk, dv)), jnp.float32)
    return (q, k, v, g, beta), state


@pytest.mark.parametrize("carried", [False, True], ids=["zero", "carried"])
@pytest.mark.parametrize("t,chunk", [
    (1, 16), (3, 16), (16, 16), (32, 16), (45, 16),
    (130, 64), (300, 64), (130, 128), (300, 128), (20, 64)])
def test_chunked_scan_equals_the_recurrence(t, chunk, carried):
    """A rectangular state and a scalar decay, betas above 1: lengths that
    are and are not whole chunks of 16, one shorter than a sublane tile;
    chunks of 64 and 128 in blocks of 8 (the inverse joins three and four
    levels of blocks by matmuls), several chunks and a last one
    part-filled; 20 positions under a chunk of 64: one chunk of THREE blocks,
    the last joined a level after the first two; from a zero and from a
    carried state."""
    args, state = _drawn(t, 0.3, seed=t + chunk - 16)
    assert float(jnp.max(args[4])) > 1.0 or t == 1
    state = state if carried else None
    want, s_want = _recurrence(*args, state)
    got, s_got = kda_scan(*args, chunk, 8, state)
    np.testing.assert_allclose(got, want, atol=5e-6)
    np.testing.assert_allclose(s_got, s_want, atol=5e-6)
    # (the correction is real: without it the outputs differ)
    plain, _ = faults._uncorrected()[0](*args, chunk, 8, state)
    assert t == 1 or float(jnp.max(jnp.abs(plain - want))) > 1e-2


@pytest.mark.parametrize("decay", ["head", "channel"])
def test_the_scans_gradient_is_the_recurrences(decay):
    """The trainer's path: the gradient of a scalar loss through the
    chunked form (autodiff through its matmuls and the pass over the
    chunks) is the token-by-token recurrence's, for every input and the
    carried state, with one decay a head and with one a channel."""
    (q, k, v, g, beta), state = _drawn(70, 0.3, seed=21)
    if decay == "channel":
        g = g * jnp.linspace(0.5, 1.5, q.shape[-1])
    mix = jnp.asarray(np.random.default_rng(2).normal(
        size=v.shape[-1:]), jnp.float32)

    def loss(scan, args):
        o, s = scan(*args)
        return jnp.sum(jnp.tanh(o) * mix) + jnp.sum(s * s) / s.size

    args = (q, k, v, g, beta, state)
    want = jax.grad(lambda a: loss(_recurrence, a))(args)
    got = jax.grad(lambda a: loss(
        lambda q, k, v, g, beta, s: kda_scan(q, k, v, g, beta, 32, 8, s),
        a))(args)
    for name, x, y in zip("q k v g beta state".split(), got, want):
        scale = float(jnp.max(jnp.abs(y)))
        assert scale > 1e-3, name
        assert float(jnp.max(jnp.abs(x - y))) < 2e-5 * max(scale, 1.0), name


def test_the_scalar_decay_is_the_channel_decay_with_equal_channels():
    """ONE implementation for both families: the scalar path ([.., 1]) and
    the per-channel path given the same decay on every channel agree."""
    (q, k, v, g, beta), state = _drawn(37, 0.5, seed=11)
    got, s_got = kda_scan(q, k, v, g, beta, 16, 4, state)
    wide = jnp.broadcast_to(g, q.shape)
    want, s_want = kda_scan(q, k, v, wide, beta, 16, 4, state)
    np.testing.assert_allclose(got, want, atol=5e-6)
    np.testing.assert_allclose(s_got, s_want, atol=5e-6)


def test_a_chunk_whose_decay_passes_e_minus_88_stays_finite_and_right():
    """``1 / Gamma`` would overflow float32 here: the cumulative log-decay
    of a chunk of 64 passes -88 on every head (and -6 a token inside one
    stretch).  The scalar form exponentiates differences ``G_i - G_j <= 0``
    only: finite, and equal to the recurrence within float32's rounding."""
    (q, k, v, g, beta), state = _drawn(100, 3.0, seed=3)
    g = g.at[:, 20:36, 0].set(-6.0)
    total = jnp.max(jnp.sum(g[:, :64], axis=1))      # the LEAST decayed
    assert float(total) < -88
    with np.errstate(over="ignore"):
        assert not np.isfinite(np.exp(-np.float32(jnp.min(
            jnp.sum(g[:, :64], axis=1)))))           # 1 / Gamma
    want, s_want = _recurrence(q, k, v, g, beta, state)
    got, s_got = kda_scan(q, k, v, g, beta, 64, 8, state)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(s_got, s_want, atol=2e-5, rtol=2e-5)


def test_a_padded_position_is_the_identity():
    """g = 0 and beta = 0 behind the real positions: the state is the last
    real position's, whatever the padded rows' q, k and v."""
    (q, k, v, g, beta), state = _drawn(13, 0.3, seed=5)
    _, s_want = kda_scan(q[:, :9], k[:, :9], v[:, :9], g[:, :9],
                         beta[:, :9], 8, 8, state)
    g = g.at[:, 9:].set(0.0)
    beta = beta.at[:, 9:].set(0.0)
    o, s_got = kda_scan(q, k, v, g, beta, 8, 8, state)
    np.testing.assert_allclose(s_got, s_want, atol=1e-6)
    assert bool(jnp.all(jnp.isfinite(o)))


# ----------------------------------------------- the step kernel

# (slots, fresh, slots in the pool, heads, pool heads a block): PR 47's
# nine row patterns, at head counts no block of 16 divides
KERNEL_CASES = {
    "full_batch": ([0, 1, 2, 3], [0, 0, 0, 0], 4, 6, 1),
    "padded_rows_among_live": ([2, 6, 0, 6, 4], [0, 0, 0, 0, 0], 6, 6, 3),
    "padded_first_and_last": ([5, 1, 3, 5], [0, 0, 0, 0], 5, 6, 1),
    "fresh_rows": ([1, 0, 3, 2], [1, 0, 0, 1], 4, 6, 3),
    "fresh_beside_padded": ([4, 4, 2, 0], [1, 0, 1, 0], 4, 6, None),
    "rows_out_of_slot_order": ([3, 0, 2, 1], [0, 0, 0, 0], 4, 6, 1),
    "more_slots_than_rows": ([5, 1], [0, 0], 7, 6, 3),
    "an_odd_number_of_blocks": ([2, 4, 0], [0, 1, 0], 4, 10, 1),
    "every_row_padded": ([3, 3], [0, 0], 3, 6, 1),
}


@pytest.mark.parametrize("pack", [1, 2], ids=["one_head", "two_side_by_side"])
@pytest.mark.parametrize("case", KERNEL_CASES)
def test_the_step_kernel_is_the_recurrence_and_touches_the_running_rows_alone(
        case, pack):
    """``ops/delta_rule.py kda_step`` (interpreted) on layer 1 of a pool of
    3 layers, rectangular states of 8 x 24 with ONE decay a head and betas
    to 2, one head's state a pool head and two heads' side by side on the
    lanes (the layout of 30 heads of 96 x 192), against the token-by-token
    recurrence: a live row's ``o`` and its slot's new state to 1e-6, a
    padded row's ``o`` zeros, and every slot no row names and every OTHER
    layer BIT-EQUAL to what it held; the by-slot ``jnp`` form gives the
    same."""
    from ray_tpu.ops import delta_rule

    slots, fresh, n_slots, h, block = KERNEL_CASES[case]
    dk, dv = 8, 24
    (q, k, v, g, beta), _ = _drawn(1, 0.3, seed=3, b=len(slots), h=h,
                                   dk=dk, dv=dv)
    q, k, v, g, beta = (x[:, 0] for x in (q, k, v, g, beta))
    q = q * dk ** -0.5               # as the mixer scales it
    logical = jnp.asarray(np.random.default_rng(2).normal(
        size=(3, n_slots, h, dk, dv)), jnp.float32)
    pool = delta_rule.pack_states(logical, h // pack)
    assert pool.shape == (3, n_slots, h // pack, dk, pack * dv)
    np.testing.assert_array_equal(delta_rule.unpack_states(pool, h), logical)
    args = (pool, 1, jnp.asarray(slots), jnp.asarray(fresh, bool), q, k, v,
            jnp.exp(g), beta)
    o, new = delta_rule.kda_step(*args, block_heads=block, interpret=True)
    o_slab, new_slab = kda_step(*args)
    assert o.shape == (len(slots), h, dv) and new.shape == pool.shape
    unpacked = delta_rule.unpack_states(new, h)
    live = [i for i, s in enumerate(slots) if s < n_slots]
    for i, s in enumerate(slots):
        if i not in live:
            np.testing.assert_array_equal(o[i], 0.0)
            continue
        start = jnp.zeros_like(logical[1, s]) if fresh[i] else logical[1, s]
        want, s_want = _recurrence(*(x[i:i + 1, None]
                                     for x in (q, k, v, g, beta)),
                                   start[None])
        np.testing.assert_allclose(o[i], want[0, 0], atol=1e-6)
        np.testing.assert_allclose(unpacked[1, s], s_want[0], atol=1e-6)
    idle = [s for s in range(n_slots) if s not in slots]
    np.testing.assert_array_equal(new[1, idle], pool[1, idle])
    np.testing.assert_array_equal(new[jnp.asarray([0, 2])],
                                  pool[jnp.asarray([0, 2])])
    np.testing.assert_allclose(o, o_slab, atol=1e-6)
    np.testing.assert_allclose(new, new_slab, atol=1e-6)


def test_the_pools_layout_follows_the_kernels_by_shape_alone():
    """``state_shape``: 30 heads of 96 x 192 lie as 15 pairs of 96 x 384
    (whole tiles, no padding) and the kernel takes them; Kimi-Linear's 32
    heads of 128 x 128 lie as they did; the tiny preset's 12 x 24 states
    stay one a pool head and on the ``jnp`` form."""
    from ray_tpu.ops.delta_rule import state_shape, supported

    def pool(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct((2, 4) + tuple(shape), dtype)

    def q(h, dk):
        return jax.ShapeDtypeStruct((2, h, dk), jnp.float32)

    assert state_shape(30, 96, 192) == (15, 96, 384)
    assert state_shape(32, 128, 128) == (32, 128, 128)
    assert state_shape(6, 12, 24) == (6, 12, 24)
    assert state_shape(6, 12, 64) == (3, 12, 128)
    assert state_shape(5, 96, 192) == (5, 96, 192)      # no pair: odd heads
    assert supported(pool((15, 96, 384)), q(30, 96))
    assert supported(pool((32, 128, 128)), q(32, 128))
    assert not supported(pool((5, 96, 192)), q(5, 96))
    assert not supported(pool((6, 12, 24)), q(6, 12))
    assert not supported(pool((15, 96, 384), jnp.bfloat16), q(30, 96))
    spec = MODEL_FAMILIES["olmohybrid"].cache(OlmoHybridConfig())
    assert spec.ssm_shape == (15, 96, 384)
    assert spec.conv_shape == (3, 11520)


# ------------------------------------------- through the engine's programs

def _against_reference(config, params, prompts, served, logits, n):
    for prompt, toks, rows in zip(prompts, served, logits):
        want = np.asarray(ref.forward(
            config, params, jnp.asarray([list(prompt) + toks[:-1]],
                                        jnp.int32)))[0][len(prompt) - 1:]
        assert len(want) == len(rows) == n
        np.testing.assert_allclose(np.stack(rows), want, atol=1e-4)


@pytest.mark.parametrize("d_v", [24, 64], ids=["one_head", "pairs_of_128"])
def test_prefill_then_decode_equals_reference_through_both_pools(d_v):
    """Three sequences of unequal length, each prefilled padded to its
    bucket (7 -> 8, 31 -> 32, 4 -> 8 positions: the chunked scan over one
    and four chunks of 8, the padding behind the real positions, the state
    and the window stored at the prompt's length into slots that held
    other numbers; the K/V prefill among its own rows storing into pages
    that held other numbers), then decoded together in a batch of 6 rows
    of which row 1 and row 5 are empty (the recurrence once a row over its
    slot; the paged attention over pages of 4 positions), 10 tokens.  At
    every generated position the logits equal the reference's full forward
    over prompt + generated tokens; with values of 64 the state pool holds
    two heads side by side ([.., 3, 12, 128]).  A larger batch with more
    padding gives the same."""
    cfg = dataclasses.replace(CFG, gdn_value_dim=d_v)
    config = dict(CONFIG, linear_value_head_dim=d_v)
    params = _scaled(olmo_hybrid_init(cfg, jax.random.PRNGKey(7)))
    spec = MODEL_FAMILIES["olmohybrid"].cache(cfg)
    assert spec.ssm_shape == ((6, 12, 24) if d_v == 24 else (3, 12, 128))
    served, logits = faults.serve(cfg, params, PROMPTS, 10, max_batch=6,
                                  page=4)
    _against_reference(config, params, PROMPTS, served, logits, 10)
    served9, logits9 = faults.serve(cfg, params, PROMPTS, 10, max_batch=9,
                                    page=4)
    assert served9 == served
    for a, b in zip(logits, logits9):
        np.testing.assert_allclose(np.stack(a), np.stack(b), atol=1e-5)


@pytest.fixture(scope="module")
def as_it_is(params):
    return faults.serve(CFG, params, PROMPTS, 8, max_batch=6, page=4)


@pytest.mark.parametrize("name", faults.FAULTS)
def test_each_fault_moves_the_logits(params, as_it_is, name):
    """Every fault of benchmark/tools/olmo_hybrid_faults.py, served the
    right program's tokens, moves some generated position's logits by far
    more than the program lies from its reference (1e-4); and the patch
    is undone after it."""
    served, logits = as_it_is
    with faults.fault(name, CFG, params) as (cfg, p, how):
        _, wrong = faults.serve(cfg, p, PROMPTS, 8, max_batch=6, page=4,
                                forced=served, **how)
    apart = max(float(np.max(np.abs(np.stack(a) - np.stack(b))))
                for a, b in zip(logits, wrong))
    assert apart > 0.1, (name, apart)
    _, again = faults.serve(CFG, params, PROMPTS[:1], 3, max_batch=6,
                            page=4)
    np.testing.assert_allclose(np.stack(again[0]), np.stack(logits[0][:3]),
                               atol=1e-6)


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


def test_the_engine_serves_it_through_both_pools_without_an_edit(params):
    """``llm/engine.py`` and ``llm/kv_cache.py`` carry the family as they
    are: a K/V pool for the 2 attention layers beside ``conv`` + ``ssm``
    for the 6 GDN layers; the engine's stream is the jitted forward's (and
    so the reference's); a slot that changes hands and an eviction's
    re-prefill reproduce it; stats() carry both pools' counters."""
    engine = _engine(params, max_batch=4)
    assert list(engine.cache.paged) == ["k_pages", "v_pages"]
    assert engine.cache.paged["k_pages"].shape == (2, 64, 4, 60)
    assert engine.cache.state["conv"].shape == (6, 4, 3, 288)
    assert engine.cache.state["ssm"].shape == (6, 4, 6, 12, 24)
    assert engine.cache.state["ssm"].dtype == jnp.float32
    out = _run(engine, (PROMPTS[0], 9), (PROMPTS[2], 9))
    served, _ = faults.serve(CFG, params, [PROMPTS[0], PROMPTS[2]], 9,
                             page=4)
    assert out == served
    stats = engine.stats()
    state, att = stats["state"], stats["attention"]
    runs = state["decode_runs"]
    assert runs == att["decode_runs"] == 8          # 9 tokens: 1 + 8 steps
    assert state["state_rows_updated"] == 2 * 6 * runs  # 6 GDN layers
    assert state["state_row_bytes"] == (3 * 288 + 6 * 12 * 24) * 4
    assert state["mixer_weight_bytes"] == CFG.mixer_params() * 4 \
        == (60 * 6 * (2 * 12 + 3 * 24) + 2 * 60 * 6 + 4 * 288) * 4
    assert att["kv_row_bytes"] == 2 * 60 * 4        # K and V, float32 here
    assert engine.stats()["state"]["slots_used"] == 0
    again = _run(engine, (PROMPTS[1], 6))
    assert again == _run(_engine(params), (PROMPTS[1], 6))
    requests = ((PROMPTS[0], 20), (PROMPTS[2], 20))
    tight = _engine(params, num_pages=10)
    out = _run(tight, *requests)
    assert tight.stats()["evictions"] > 0
    assert out == _run(_engine(params), *requests)


def _lowered(cfg, shape, platforms=None):
    from ray_tpu.llm.engine import jit_forward
    from ray_tpu.llm.kv_cache import init_pool, init_state, pages_for

    spec = MODEL_FAMILIES["olmohybrid"].cache(cfg)
    params = jax.eval_shape(
        lambda: olmo_hybrid_init(cfg, jax.random.PRNGKey(0)))
    kv = jax.eval_shape(lambda: init_pool(spec, 16, 4, cfg.dtype))
    state = jax.eval_shape(lambda: init_state(spec, 2, cfg.dtype))
    ints = jax.ShapeDtypeStruct(shape, jnp.int32)
    traced = jit_forward(OlmoHybrid(cfg)).trace(
        params, ints, kv["k_pages"], kv["v_pages"], jax.ShapeDtypeStruct(
            (shape[0], pages_for(cfg.max_seq, 4)), jnp.int32),
        ints, state["conv"], state["ssm"],
        jax.ShapeDtypeStruct(shape[:1], jnp.int32))
    lowered = traced.lower() if platforms is None \
        else traced.lower(lowering_platforms=platforms)
    return lowered.as_text(debug_info=True)


def test_the_lowered_forward_names_the_scopes_the_readers_file_by():
    """benchmark/harness/gdn_phases.py files a trace's operations by these
    names: a decode step has ``gdn.step`` and ``kv.attend`` and no
    ``gdn.scan``, a prefill ``gdn.scan`` and NO ``kv.attend`` (it attends
    among its own rows and reads nothing from the pool); no rotation
    anywhere; the QK-norm under ``attn.qk_norm``."""
    decode, prefill = _lowered(CFG, (2, 1)), _lowered(CFG, (1, 16))
    both = ("gdn.proj", "gdn.conv", "gdn.gate", "gdn.out_norm",
            "gdn.out_proj", "attn.qkv", "attn.qk_norm", "attn.core",
            "kv.store", "attn.out", "mlp", "lm_head")
    for name in both + ("gdn.step", "kv.attend"):
        assert name in decode, name
    for name in both + ("gdn.scan",):
        assert name in prefill, name
    assert "gdn.scan" not in decode and "gdn.step" not in prefill
    assert "kv.attend" not in prefill
    assert "stablehlo.sine" not in decode + prefill           # no rope
    assert "mlp.dense" not in decode                # no experts to set apart
    # the chunked scan is matmuls and one loop: no triangular solve
    assert "triangular_solve" not in prefill + decode
    assert "stablehlo.while" in prefill
    assert "tpu_custom_call" not in decode      # 12 x 24 states: ``jnp``


def test_the_lowered_decode_step_holds_the_kernel_the_reader_files_by_name():
    """benchmark/harness/gdn_phases.py files an instruction whose name
    starts with ``kda_step`` under ``gdn.step``: with states of 96 x 192,
    lowered for the ``tpu`` platform as that backend dispatches, the decode
    forward calls one such custom call once a GDN layer on the pool of
    pairs [.., 3, 96, 384], and its prefill none."""
    import ray_tpu.models.kimi_linear as kimi_linear
    from ray_tpu.ops import delta_rule

    cfg = dataclasses.replace(CFG, gdn_key_dim=96, gdn_value_dim=192)
    with pytest.MonkeyPatch.context() as patch:    # as on the tpu backend
        patch.setattr(kimi_linear, "_step_kernel", delta_rule.supported)
        patch.setattr(delta_rule, "kda_step", functools.partial(
            delta_rule.kda_step, interpret=False))
        decode = _lowered(cfg, (2, 1), ("tpu",))
        prefill = _lowered(cfg, (1, 16), ("tpu",))
    # (the layer is an operand: ONE lowered function, called a GDN layer)
    call, = [x for x in decode.splitlines()
             if "stablehlo.custom_call @tpu_custom_call" in x]
    assert 'kernel_name = "kda_step"' in call
    assert "output_operand_alias" in call and "operand_index = 5" in call
    assert "x3x96x384xf32" in call
    assert len([x for x in decode.splitlines()
                if "call @_kda_step(" in x]) == cfg.layers_of(GDN) == 6
    assert any("gdn.step/jit(_kda_step)" in x for x in decode.splitlines())
    assert "tpu_custom_call" not in prefill and "gdn.scan" in prefill


# ---------------------------------------------------------- the registry

def test_the_registry_builds_the_ninth_family():
    row = MODEL_FAMILIES["olmohybrid"]
    assert len(MODEL_FAMILIES) == 10 and row.config is OlmoHybridConfig
    assert family_of(CFG) is row and row.module is OlmoHybrid
    assert row.cache(CFG) == CacheSpec(
        kv_layers=2, kv_heads=6, head_dim=10, state_layers=6,
        conv_shape=(3, 288), ssm_shape=(6, 12, 24))
    published = OlmoHybridConfig()
    assert published.layer_types == (GDN, GDN, GDN, ATTENTION) * 8
    assert (published.mixer_params(), published.attention_params()) == (
        88_750_080, 58_982_400)
    assert row.cache(published) == CacheSpec(
        kv_layers=8, kv_heads=30, head_dim=128, state_layers=24,
        conv_shape=(3, 11520), ssm_shape=(15, 96, 384))
    with pytest.raises(ValueError, match="layer_types"):
        OlmoHybridConfig(layer_types=("mamba",))
    from ray_tpu.train import rules_for_model

    rules = rules_for_model("olmo_hybrid")
    assert any("wg" in pattern for pattern, _ in rules)
