"""Granite 4.0-H (models/granite.py: Mamba-2 mixers beside attention, a
share of the routed experts plus a shared one) held to its plain float32
reference (benchmark/reference/granitemoehybrid_ref.py) at a tiny size on
the CPU: [mamba, mamba, attention, mamba], 64 wide, 4 Mamba heads of 16
with state 16 and chunks of 8, top-2 of 8 experts of width 32.  Through
the model, the chunked scan, the engine's jitted forward with BOTH caches
(the paged K/V pool and the state pool), the engine's slots and counters,
the expert shares, the loss and the family registry."""

import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import granitemoehybrid_ref as ref
from benchmark.tools import granite_faults
from ray_tpu.models import MODEL_FAMILIES, family_of
from ray_tpu.models.granite import (Granite, GraniteConfig, granite_init,
                                    granite_loss_fn, ssd_scan)

CFG = GraniteConfig.tiny(remat=False)
# The reference's configuration: the source's keys at the tiny size.
CONFIG = {"num_hidden_layers": 4, "layer_types": list(CFG.layer_types),
          "hidden_size": 64, "num_attention_heads": 4,
          "num_key_value_heads": 2, "num_local_experts": 8,
          "num_experts_per_tok": 2, "mamba_n_heads": 4, "mamba_d_head": 16,
          "mamba_d_state": 16, "mamba_d_conv": 4,
          "embedding_multiplier": 12.0, "residual_multiplier": 0.22,
          "attention_multiplier": 0.0625, "logits_scaling": 16.0,
          "rms_norm_eps": 1e-5}


def _scaled(params, factor=8.0):
    """std-0.02 weights at 64 wide leave every router near-uniform and
    the attention flat; scaled up, routing is decided and an error of the
    mathematics shows (tests/test_olmoe.py).  The embedding, drawn at
    0.02 / 12, twelve times more: logits of size ~0.1."""
    return jax.tree_util.tree_map_with_path(
        lambda path, w: w if w.ndim == 1 else factor * w * (
            12 if path[-1].key == "embed" else 1), params)


def _share(params, config, first, count):
    """The tree and the reference's configuration of the chip that holds
    experts ``first`` .. ``first + count`` of every layer."""
    p = copy.deepcopy(jax.tree_util.tree_map(np.asarray, params))
    for layer in p["params"].values():
        if isinstance(layer, dict) and "moe" in layer:
            for name in ("w_gate", "w_up", "w_down"):
                layer["moe"][name] = layer["moe"][name][first:first + count]
    return p, dict(config, num_local_experts=count,
                   first_local_expert=first,
                   published={"num_local_experts":
                              config["num_local_experts"]})


@pytest.fixture(scope="module")
def params():
    return _scaled(granite_init(CFG, jax.random.PRNGKey(7)))


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 27)),
                       jnp.int32)


def _apply(cfg, params, tokens):
    return jax.jit(lambda p, t: Granite(cfg).apply(p, t))(params, tokens)


# ------------------------------------------------ forward against reference

@pytest.mark.parametrize("first,count", [(0, 8), (2, 3)],
                         ids=["all_experts", "experts_2_to_4"])
def test_forward_equals_reference(params, tokens, first, count):
    """The full forward (27 positions: three chunks of 8 and a rest)
    against the token-by-token reference, logits of size ~1; holding all
    the experts, and holding three of the eight, where both leave out
    what the absent five would add."""
    cfg = CFG if count == 8 else dataclasses.replace(
        CFG, first_expert=first, held_experts=count)
    p, config = (params, CONFIG) if count == 8 else _share(
        params, CONFIG, first, count)
    want = ref.forward(config, p, tokens)
    assert float(jnp.std(want)) > 0.05
    np.testing.assert_allclose(_apply(cfg, p, tokens), want, atol=2e-5)
    if count != 8:      # the share is not the whole
        whole = ref.forward(CONFIG, params, tokens)
        assert float(jnp.max(jnp.abs(whole - want))) > 1e-2


def _recurrence(x, dt, a, b_mat, c_mat, state):
    """S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T; y_t = S_t C_t, in
    float64 numpy, one position at a time."""
    x, dt, a, b_mat, c_mat, s = (np.asarray(z, np.float64) for z in
                                 (x, dt, a, b_mat, c_mat, state))
    ys = []
    for t in range(x.shape[1]):
        s = np.exp(dt[:, t] * a)[..., None, None] * s \
            + (dt[:, t, :, None] * x[:, t])[..., None] \
            * b_mat[:, t, None, None, :]
        ys.append(np.einsum("bhpn,bn->bhp", s, c_mat[:, t]))
    return np.stack(ys, axis=1), s


@pytest.mark.parametrize("t", [1, 5, 8, 13, 27])
def test_chunked_scan_equals_the_recurrence(t):
    """``ssd_scan`` in chunks of 8 against the recurrence token by token,
    at lengths that are and are not multiples of the chunk, from a
    non-zero state, with decays from ~1 to ~e^-20 a step."""
    rng = np.random.default_rng(t)
    b, h, p, n = 2, 4, 6, 5
    x = rng.normal(size=(b, t, h, p)).astype(np.float32)
    dt = rng.uniform(0.001, 2.0, size=(b, t, h)).astype(np.float32)
    a = -np.array([0.01, 1.0, 4.0, 10.0], np.float32)
    b_mat, c_mat = rng.normal(size=(2, b, t, n)).astype(np.float32)
    state = rng.normal(size=(b, h, p, n)).astype(np.float32)
    want_y, want_s = _recurrence(x, dt, a, b_mat, c_mat, state)
    y, s = jax.jit(lambda *z: ssd_scan(*z[:5], 8, z[5]))(
        x, dt, a, b_mat, c_mat, state)
    np.testing.assert_allclose(y, want_y, atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(s, want_s, atol=2e-5, rtol=1e-4)


def test_a_padded_position_neither_decays_nor_feeds_the_state():
    """dt = 0 marks padding: behind the real positions (a prefill padded
    to its bucket) it leaves the state of the last real one."""
    rng = np.random.default_rng(3)
    b, t, real, h, p, n = 1, 16, 11, 4, 6, 5
    x = rng.normal(size=(b, t, h, p)).astype(np.float32)
    dt = rng.uniform(0.01, 1.0, size=(b, t, h)).astype(np.float32)
    dt[:, real:] = 0.0
    a = -np.array([0.1, 1.0, 2.0, 8.0], np.float32)
    b_mat, c_mat = rng.normal(size=(2, b, t, n)).astype(np.float32)
    y, s = ssd_scan(x, dt, a, b_mat, c_mat, 8)
    y_real, s_real = ssd_scan(x[:, :real], dt[:, :real], a,
                              b_mat[:, :real], c_mat[:, :real], 8)
    np.testing.assert_allclose(s, s_real, atol=1e-6)
    np.testing.assert_allclose(y[:, :real], y_real, atol=1e-6)


# ------------------------------- engine: both caches, slots, the counters

PROMPTS = ([3, 17, 42, 99, 7, 250, 8], [9] * 19, [5, 1, 200, 31, 64])


def test_prefill_then_decode_equals_reference_through_both_caches(params):
    """Three sequences, each prefilled padded to its bucket (7 -> 8, 19 ->
    32, 5 -> 8 positions) into slots that held other numbers, then decoded
    together in a batch of 6 rows of which row 1 and row 5 are empty: at
    every generated position the logits through prefill, the K/V pool,
    the state pool and batched decode equal the reference's full forward
    over prompt + generated tokens.  A larger batch with more padding
    gives the same."""
    served, logits = granite_faults.serve(CFG, params, PROMPTS, 6,
                                          jnp.float32, max_batch=6)
    for prompt, toks, rows in zip(PROMPTS, served, logits):
        want = np.asarray(ref.forward(
            CONFIG, params, jnp.asarray([list(prompt) + toks[:-1]],
                                        jnp.int32)))[0][len(prompt) - 1:]
        assert len(want) == len(rows) == 6
        np.testing.assert_allclose(np.stack(rows), want, atol=5e-5)
    served9, logits9 = granite_faults.serve(CFG, params, PROMPTS, 6,
                                            jnp.float32, max_batch=9)
    assert served9 == served
    for a, b in zip(logits, logits9):
        np.testing.assert_allclose(np.stack(a), np.stack(b), atol=1e-6)


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


def test_a_slot_taken_again_serves_as_a_fresh_engine_does(params):
    """Slots change hands without being cleared: the second sequence in
    slot 0 (and the third, in a slot another sequence left mid-stream by
    cancellation) get the tokens a fresh engine gives them."""
    engine = _engine(params)
    first = _run(engine, (PROMPTS[1], 9))
    assert engine.stats()["state"]["slots_used"] == 0
    again = _run(engine, (PROMPTS[0], 9), (PROMPTS[2], 9))
    assert again == _run(_engine(params), (PROMPTS[0], 9), (PROMPTS[2], 9))
    assert first == _run(_engine(params), (PROMPTS[1], 9))
    cut = engine.submit(list(PROMPTS[1]), max_tokens=30)
    for _ in range(4):
        engine.step()
    assert engine.stats()["state"]["slots_used"] == 1 and cut.held.slot == 0
    engine.cancel(cut.sid)
    engine.step()
    assert cut.finished and cut.held.slot is None
    assert engine.stats()["state"]["slots_used"] == 0
    assert _run(engine, (PROMPTS[0], 9)) == [again[0]]


def test_eviction_and_re_prefill_reproduce_the_stream(params):
    """A pool too small for two sequences at their full lengths forces
    recompute preemption: the victim gives back its pages AND its slot,
    re-prefills prompt + generated (which rebuilds the state), and both
    streams are what a roomy engine serves."""
    requests = ((PROMPTS[0], 20), (PROMPTS[2], 20))
    tight = _engine(params, num_pages=10)
    tokens = _run(tight, *requests)
    stats = tight.stats()
    assert stats["evictions"] > 0
    assert stats["kv_pages_used"] == 0 and stats["state"]["slots_used"] == 0
    assert tokens == _run(_engine(params), *requests)


def test_engine_counts_what_the_state_and_the_experts_moved(params):
    """stats()["state"]: a slot a running sequence; state_rows_updated =
    running rows x the 3 state-space layers, summed over the decode runs;
    one row = a conv window (3 x 96 float32) and a state (4 x 16 x 16
    float32) of one layer.  stats()["moe"] counts the HELD experts: with
    experts 2-4 of 8 held, fewer pairs than rows x k x layers.  The K/V
    rows are counted over the ONE attention layer."""
    held = dataclasses.replace(CFG, first_expert=2, held_experts=3)
    p, _ = _share(params, CONFIG, 2, 3)
    from ray_tpu.llm.engine import EngineConfig, GenerationEngine

    engine = GenerationEngine(
        model_cfg=held, params=p,
        engine_cfg=EngineConfig(page_size=4, num_pages=64, max_batch=4))
    for prompt in PROMPTS[:2]:
        engine.submit(list(prompt), max_tokens=5)
    engine.step()
    assert engine.stats()["state"]["slots_used"] == 2
    while engine.stats()["running"]:
        engine.step()
    stats = engine.stats()
    state, moe, att = stats["state"], stats["moe"], stats["attention"]
    runs = state["decode_runs"]
    assert state["slots_total"] == 4 and state["slots_used"] == 0
    assert runs == att["decode_runs"] == 4          # 5 tokens: 1 + 4 steps
    assert state["state_rows_updated"] == 2 * 3 * runs
    assert state["state_row_bytes"] == (3 * 96 + 4 * 16 * 16) * 4
    assert state["mixer_weight_bytes"] == (64 * (64 + 96 + 4) + 64 * 64) * 4
    assert moe["layer_runs"] == 4 * runs            # every layer has experts
    assert 0 < moe["pairs"] < 2 * 2 * 4 * runs      # rows x k x layers
    assert moe["experts_hit"] <= 3 * moe["layer_runs"]
    # pages of 4 positions up to each length, over 1 layer with K/V
    want = sum(-(-(n + i) // 4) * 4
               for n in (len(PROMPTS[0]), len(PROMPTS[1]))
               for i in range(1, 5))
    assert att["kv_rows_read"] == want
    assert att["kv_rows_held"] == runs * 4 * engine.cache.pages_per_seq * 4
    assert "state" not in GenerationEngine(model="olmoe").stats()


# --------------------------------------------------- the shares add up

def _lfm2_layer():
    """One sparse layer of a tiny LFM2 (sigmoid scores, a selection bias
    that is not zero, ``s / (sum + 1e-6)``), its reference and config."""
    from benchmark.reference import lfm2_moe_ref
    from ray_tpu.models.lfm2 import Lfm2Config, lfm2_init

    tree = _scaled(lfm2_init(Lfm2Config.tiny(), jax.random.PRNGKey(7)))
    config = {"num_experts": 8, "num_experts_per_tok": 2}
    return tree["params"]["layer_2"], lfm2_moe_ref, config, dict(
        scoring="sigmoid", select_bias=True, norm_eps=1e-6)


@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
def test_the_four_expert_shares_and_the_shared_expert_add_up(params,
                                                             scoring):
    """Expert parallelism over four chips of two experts each, on one
    layer's input: the routed parts the four shares compute (ops/moe.py
    told which experts it holds) plus, where the model has one, the shared
    expert counted ONCE equal the uncut reference's whole layer.  Under
    softmax scoring (Granite: ``MoE(h) + Shared(h)``) and under sigmoid
    scoring with a selection bias (models/lfm2.py: the renormalisation is
    over the k CHOSEN, held here or not; no shared expert)."""
    import flax.linen as nn

    from ray_tpu.ops.moe import MoEMLP

    if scoring == "softmax":
        layer, reference, config, how = (params["params"]["layer_1"], ref,
                                         CONFIG, {})
    else:
        layer, reference, config, how = _lfm2_layer()
    h = jnp.asarray(np.random.default_rng(5).normal(size=(1, 23, 64)),
                    jnp.float32)
    parts = []
    for rank in range(4):
        moe = dict(layer["moe"])
        for name in ("w_gate", "w_up", "w_down"):
            moe[name] = moe[name][2 * rank:2 * rank + 2]
        op = MoEMLP(d_model=64, d_ff=32, num_experts=8, top_k=2, gated=True,
                    norm_topk_prob=True, act=nn.silu, dtype=jnp.float32,
                    first_expert=2 * rank, held_experts=2, **how)
        y, sown = op.apply({"params": moe}, h, mutable=["intermediates"])
        (m,) = sown["intermediates"]["moe"]
        assert m["load"].shape == (2,)      # the held experts only
        parts.append((y, int(jnp.sum(m["load"]))))
    assert sum(n for _, n in parts) == 23 * 2       # every pair, once
    assert all(float(jnp.max(jnp.abs(y))) > 0 for y, _ in parts)
    flat = h.reshape(23, 64)
    shared = ref._shared(flat, layer) if scoring == "softmax" else 0.0
    want = reference._experts_eager(flat, layer["moe"], config) + shared
    got = sum(y for y, _ in parts).reshape(23, 64) + shared
    np.testing.assert_allclose(got, want, atol=2e-5)


# -------------------------------------------------------------- training

def test_loss_and_every_gradient_leaf_equal_the_reference(params, tokens):
    loss, grads = jax.jit(lambda p: jax.value_and_grad(
        lambda q: granite_loss_fn(CFG, q, {"tokens": tokens}))(p))(params)
    want, want_grads = jax.jit(
        lambda p: ref.loss_and_grads(CONFIG, p, tokens))(params)
    assert abs(float(loss) - float(want)) < 1e-5
    flat = jax.tree_util.tree_leaves_with_path(grads)
    assert len(flat) == len(jax.tree_util.tree_leaves(params)) > 60
    for (path, g), r in zip(flat, jax.tree_util.tree_leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(r)))
        assert scale > 0, jax.tree_util.keystr(path)
        assert float(jnp.max(jnp.abs(g - r))) < 2e-4 * scale, \
            jax.tree_util.keystr(path)


# -------------------------------------------------------------- registry

def test_the_registry_builds_the_fourth_family():
    row = MODEL_FAMILIES["granitemoehybrid"]
    assert len(MODEL_FAMILIES) == 10 and row.config is GraniteConfig
    assert family_of(row.tiny()).module is Granite
    spec = row.cache(GraniteConfig())       # as published: 36 + 4 layers
    assert (spec.kv_layers, spec.kv_heads, spec.head_dim) == (4, 8, 128)
    assert (spec.state_layers, spec.conv_shape, spec.ssm_shape) == (
        36, (3, 8448), (128, 64, 128))
    for name in ("gpt2", "llama", "olmoe"):     # K/V in every layer
        fam = MODEL_FAMILIES[name]
        spec = fam.cache(fam.tiny())
        assert spec.kv_layers == fam.tiny().n_layer
        assert spec.state_layers == 0


# ----------------------------------- the comparison can tell right from wrong

@pytest.fixture(scope="module")
def served_right(params):
    prompts = [list(PROMPTS[1]) + list(PROMPTS[0]) * 3, list(PROMPTS[0])]
    served, logits = granite_faults.serve(CFG, params, prompts, 12,
                                          jnp.float32)
    return prompts, served, logits


@pytest.fixture
def rotary_hook(monkeypatch):
    """The tool's ``rotary`` patches ``granite.attention``, the name the
    attention wrapper called the core by until PR 44 moved it to
    ``models/decoder.py`` (ROADMAP Design 5(c): the tool is the next
    ``benchmark`` PR's to edit; until then the name is gone and the fault
    raises AttributeError on the chip).  Lend the name and send the shared
    wrapper's call through it, so that the fault still reaches the program
    here."""
    import ray_tpu.models.decoder as decoder
    import ray_tpu.models.granite as granite

    monkeypatch.setattr(granite, "attention", decoder.attention,
                        raising=False)
    monkeypatch.setattr(decoder, "attention",
                        lambda *a, **kw: granite.attention(*a, **kw))


@pytest.mark.parametrize("name", granite_faults.FAULTS)
def test_each_fault_moves_the_served_logits(params, served_right, name,
                                            rotary_hook):
    """The five things the chip run shows to FAIL the cell's tolerance
    (benchmark/tools/granite_faults.py), here at the tiny size in float32,
    fed the right program's tokens: each moves some logit (of size ~0.1)
    by more than 500 times the 1.2e-7 that separate the right program
    from the reference (measured: the bf16 state 2.0e-4, the others 4.9e-3
    to 1.5e-2)."""
    prompts, served, right = served_right
    with granite_faults.fault(name, CFG, params) as (cfg, p, ssm_dtype):
        _, wrong = granite_faults.serve(cfg, p, prompts, 12, ssm_dtype,
                                        forced=served)
    apart = max(float(np.max(np.abs(np.stack(a) - np.stack(b))))
                for a, b in zip(right, wrong))
    assert apart > 1e-4, apart
