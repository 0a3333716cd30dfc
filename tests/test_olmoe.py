"""OLMoE (models/llama.py with QK-norm and experts, ops/moe.py) held to
its plain float32 reference (benchmark/reference/olmoe_ref.py) at a tiny
size on the CPU: 2 layers, 64 wide, 4 heads of 16, top-2 of 8 experts of
width 32, vocabulary 256.  Through the model, the loss, the engine's
jitted forward with the paged pool, the engine's counters, the trainer's
sharded step and the family registry."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import olmoe_ref
from ray_tpu.models import MODEL_FAMILIES, family_of
from ray_tpu.models.llama import (Llama, LlamaConfig, llama_init,
                                  olmoe_loss_fn)

# The reference's configuration: the source's keys at the tiny size.
CONFIG = {"num_hidden_layers": 2, "hidden_size": 64,
          "num_attention_heads": 4, "num_experts": 8,
          "num_experts_per_tok": 2, "rms_norm_eps": 1e-5,
          "rope_theta": 10000.0, "norm_topk_prob": False,
          "router_aux_loss_coef": 0.01, "router_z_loss_coef": 0.001}
CFG = LlamaConfig.olmoe_tiny(remat=False)


def _apply(cfg, params, tokens):
    """The program's full forward, jitted (eagerly it dispatches op by
    op: ten times the seconds)."""
    return jax.jit(lambda p, t: Llama(cfg).apply(p, t))(params, tokens)


def _scaled(params, factor=8.0):
    """std-0.02 weights at 64 wide leave every logit ~1e-3 and every
    router near-uniform; scaled up, routing is decided and logits are
    O(1), so an error of the mathematics shows."""
    return jax.tree_util.tree_map(
        lambda w: w if w.ndim == 1 else factor * w, params)


@pytest.fixture(scope="module")
def params():
    return _scaled(llama_init(CFG, jax.random.PRNGKey(7)))


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 25)),
                       jnp.int32)


def _near_uniform_router(params):
    """Layer 0's router scaled to 1e-3: its probabilities lie ~1e-5
    apart, so the top-2 is a choice among near-ties."""
    p = jax.tree_util.tree_map(lambda x: x, params)
    p["params"]["layer_0"]["moe"]["router"] = \
        1e-3 * p["params"]["layer_0"]["moe"]["router"]
    return p


@pytest.mark.parametrize("router", ["seeded", "near_uniform"])
def test_forward_logits_equal_reference(params, tokens, router):
    """1e-4 absolute on logits of size ~1: float32 rounding in another
    order of summation (sort and grouped matmul against a loop).  The
    near-uniform router still picks the reference's experts: both route
    in float32, from the same float32 rows."""
    p = params if router == "seeded" else _near_uniform_router(params)
    ours = _apply(CFG, p, tokens)
    ref = olmoe_ref.forward(CONFIG, p, tokens)
    assert float(jnp.max(jnp.abs(ref))) > 0.5
    assert float(jnp.max(jnp.abs(ours - ref))) < 1e-4


def test_loss_and_every_gradient_leaf_equal_reference(params, tokens):
    """The training loss (cross entropy + 0.01 x load balancing + 0.001 x
    router z) and each leaf's gradient, the router's and both QK-norm
    scales' among them, within 1e-4 of the leaf's largest entry: float32
    through two different programs (the reference runs every expert on
    every row under a mask)."""
    (loss, metrics), grads = jax.jit(jax.value_and_grad(
        lambda p: olmoe_loss_fn(CFG, p, {"tokens": tokens},
                                with_metrics=True), has_aux=True))(params)
    ref_loss, ref_grads = jax.jit(
        lambda p: olmoe_ref.loss_and_grads(CONFIG, p, tokens))(params)
    assert abs(float(loss) - float(ref_loss)) < 1e-4 * float(ref_loss)
    assert float(metrics["moe_load_balancing"]) >= 2.0   # 2 layers x >= 1
    assert float(metrics["moe_max_load_over_mean"]) >= 1.0
    flat = jax.tree_util.tree_leaves_with_path(grads)
    ref_flat = jax.tree_util.tree_leaves(ref_grads)
    assert len(flat) == len(ref_flat) == 2 * 12 + 3
    for (path, g), r in zip(flat, ref_flat):
        scale = float(jnp.max(jnp.abs(r)))
        assert scale > 0, jax.tree_util.keystr(path)
        assert float(jnp.max(jnp.abs(g - r))) < 1e-4 * scale, \
            jax.tree_util.keystr(path)


# ------------------------------------------ engine: paged pool, counters

def _engine_forward(cfg, params, prompts, steps, max_batch):
    """Prefill each prompt ([1, bucket]) and decode ``steps`` greedy
    tokens with ALL of them in one padded [max_batch, 1] batch, through
    ``jit_forward`` and one paged pool, as the engine does.  Returns the
    per-sequence logits at every generated position and the decode runs'
    counters."""
    from ray_tpu.llm.engine import jit_forward
    from ray_tpu.llm.kv_cache import init_cache, pages_for

    page = 16
    per_seq = pages_for(cfg.max_seq, page)
    kv = init_cache(cfg.n_layer, per_seq * max_batch, page, cfg.n_kv_head,
                    cfg.d_model // cfg.n_head, cfg.dtype)
    k, v = kv["k_pages"], kv["v_pages"]
    fwd = jit_forward(Llama(cfg))
    table = np.zeros((max_batch, per_seq), np.int32)
    seqs, logits_out = [list(p) for p in prompts], [[] for _ in prompts]
    for i, prompt in enumerate(prompts):
        table[i] = np.arange(per_seq) + i * per_seq
        n, pad = len(prompt), 32
        toks = np.zeros((1, pad), np.int32)
        toks[0, :n] = prompt
        pos = np.full((1, pad), -1, np.int32)
        pos[0, :n] = np.arange(n)
        logits, k, v, _ = fwd(params, toks, k, v, table[i:i + 1], pos)
        logits_out[i].append(np.asarray(logits[0, n - 1]))
        seqs[i].append(int(np.argmax(logits_out[i][-1])))
    counters = []
    for _ in range(steps - 1):
        toks = np.zeros((max_batch, 1), np.int32)
        pos = np.full((max_batch, 1), -1, np.int32)
        for i, s in enumerate(seqs):
            toks[i, 0], pos[i, 0] = s[-1], len(s) - 1
        logits, k, v, moe = fwd(params, toks, k, v, table, pos)
        counters.append(np.asarray(moe))
        for i, s in enumerate(seqs):
            logits_out[i].append(np.asarray(logits[i, 0]))
            s.append(int(np.argmax(logits_out[i][-1])))
    return seqs, logits_out, counters


PROMPTS = ([3, 17, 42, 99, 7, 250, 8], [9] * 19)


def test_prefill_then_decode_equals_reference_with_padded_batch(params):
    """Two sequences in a batch padded to 8 rows (three quarters of it
    padding): at every generated position the logits through prefill,
    the paged pool and batched decode equal the reference's full forward
    over prompt + generated tokens (2e-4: float32, attention summed over
    gathered pages in another order).  The padded rows change nothing:
    a batch padded to 4 gives the same logits and the same counters."""
    seqs, logits, counters = _engine_forward(CFG, params, PROMPTS, 5, 8)
    for prompt, seq, rows in zip(PROMPTS, seqs, logits):
        ref = np.asarray(olmoe_ref.forward(
            CONFIG, params, jnp.asarray([seq[:-1]], jnp.int32)))[0]
        want = ref[len(prompt) - 1:]
        assert len(want) == len(rows) == 5
        np.testing.assert_allclose(np.stack(rows), want, atol=2e-4)
    seqs4, logits4, counters4 = _engine_forward(CFG, params, PROMPTS, 5, 4)
    assert seqs4 == seqs
    for a, b in zip(logits, logits4):
        np.testing.assert_allclose(np.stack(a), np.stack(b), atol=1e-6)
    np.testing.assert_array_equal(np.stack(counters), np.stack(counters4))
    # per layer: 2 real rows x top-2 = 4 pairs over 2 to 4 experts
    pairs, hit, largest, compact = np.moveaxis(np.stack(counters), -1, 0)
    assert (pairs == 4).all() and not compact.any()
    assert hit.shape == (4, 2) and (hit >= 2).all() and (hit <= 4).all()
    assert (largest >= 1).all() and (largest <= 2).all()


def test_engine_counters_add_up(params):
    """stats()["moe"] over the engine's own decode runs: pairs = real rows
    x k x layers, layer_runs = runs x layers, experts_hit between one
    expert per layer-run... and min(E x layer_runs, pairs), max_load
    between pairs / experts_hit and pairs / k."""
    from ray_tpu.llm.engine import EngineConfig, GenerationEngine

    engine = GenerationEngine(
        model_cfg=CFG, params=params,
        engine_cfg=EngineConfig(max_batch=4, num_pages=32))
    for prompt in PROMPTS:
        engine.submit(list(prompt), max_tokens=4)
    while engine.stats()["tokens_generated"] < 8:
        engine.step()
    stats = engine.stats()
    moe, layers, k = stats["moe"], CFG.n_layer, CFG.experts_per_token
    assert moe["layer_runs"] % layers == 0
    decode_runs = moe["layer_runs"] // layers
    assert 3 <= decode_runs <= stats["steps"]
    # both sequences decode together in every run but possibly the last
    assert moe["pairs"] in (2 * k * layers * decode_runs,
                            2 * k * layers * (decode_runs - 1)
                            + k * layers)
    assert moe["layer_runs"] * k <= moe["experts_hit"] <= min(
        CFG.n_experts * moe["layer_runs"], moe["pairs"])
    assert moe["pairs"] / moe["experts_hit"] <= \
        moe["max_load"] / moe["layer_runs"] <= 2
    dense = GenerationEngine(model="gpt2")
    assert "moe" not in dense.stats()


@pytest.mark.parametrize("family", sorted(MODEL_FAMILIES))
def test_engine_builds_every_family_from_its_registry_row(family):
    from ray_tpu.llm.engine import EngineConfig, GenerationEngine

    row = MODEL_FAMILIES[family]
    engine = GenerationEngine(
        model=family, engine_cfg=EngineConfig(max_batch=2, num_pages=16))
    assert isinstance(engine.model_cfg, row.config)
    assert isinstance(engine._model, row.module)
    assert family_of(engine.model_cfg).module is row.module
    seq = engine.submit([1, 2, 3], max_tokens=3)
    while not seq.finished:
        engine.step()
    assert seq.generated == 3 and engine.stats()["step_errors"] == 0
    assert ("moe" in engine.stats()) == (
        family in ("olmoe", "granitemoehybrid", "lfm2moe", "kimik2",
                   "kimilinear", "xing40", "cohere2moe"))
    assert ("residual" in engine.stats()) == (family == "xing40")
    assert ("state" in engine.stats()) == (
        family in ("granitemoehybrid", "lfm2moe", "kimilinear",
                   "olmohybrid"))


def test_engine_counts_gpt2s_moe_option_too():
    """The counters come from what the forward returns, not from which
    config class has experts: GPT-2's synthetic option (top-2 of 4 in
    every second block) is counted like OLMoE."""
    import dataclasses as dc

    from ray_tpu.llm.engine import EngineConfig, GenerationEngine
    from ray_tpu.models.gpt2 import GPT2Config

    engine = GenerationEngine(
        model_cfg=dc.replace(GPT2Config.tiny(), moe_num_experts=4),
        engine_cfg=EngineConfig(max_batch=2, num_pages=16))
    seq = engine.submit([1, 2, 3], max_tokens=3)
    while not seq.finished:
        engine.step()
    moe = engine.stats()["moe"]
    assert moe["layer_runs"] == 2 * 1      # 2 decode runs x 1 MoE block
    assert moe["pairs"] == 2 * 1 * 2 and engine.stats()["step_errors"] == 0


# ------------------------------------------------- bf16 against float32

def _bf16_gaps(cfgs, params, tokens):
    """Median |logit difference| of each program on bf16 weights against
    the reference on the same bf16 values."""
    p16 = jax.tree_util.tree_map(lambda w: w.astype(jnp.bfloat16), params)
    ref = olmoe_ref.forward(CONFIG, p16, tokens)
    return [float(jnp.median(jnp.abs(_apply(cfg, p16, tokens) - ref)))
            for cfg in cfgs]


BF16_TOLERANCE = 0.03


def test_bf16_program_within_measured_tolerance_and_wrong_ones_not(
        params, tokens):
    """The program as the cell runs it (bf16 weights, activations and
    matmul inputs; float32 router, norms, softmax, logits) against the
    float32 reference on the same bf16 weights, logits of size ~2-5.
    The MEDIAN difference: a bf16 row can flip a near-tied 2nd/3rd
    expert against the float32 reference, and the few positions behind
    such a flip move by up to ~1 at this size (top-2 of 8, weights scaled
    up), which says nothing about the precision of the rest.  Measured
    over three seeds: 0.007-0.010; the tolerance is three times that.
    Two wrong programs miss it by 5 to 30 times: weights renormalised
    over the top-k (0.16-0.29), and no QK-norm (0.29-0.35)."""
    bf16 = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    right, renormalised, no_qk_norm = _bf16_gaps(
        [bf16, dataclasses.replace(bf16, norm_topk_prob=True),
         dataclasses.replace(bf16, qk_norm=False)], params, tokens)
    assert right < BF16_TOLERANCE
    assert renormalised > 5 * BF16_TOLERANCE
    assert no_qk_norm > 5 * BF16_TOLERANCE


# ------------------------------------------------------------- trainer

def test_sharded_train_step_equals_unsharded_loss(tokens):
    """The trainer's step on the virtual fsdp=2 x tensor=2 mesh, state
    placed by ``olmoe_partition_rules``: the same loss and router metrics
    as the unsharded step (1e-5: float32, partial sums in another
    order), and the experts' matrices really are sharded."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from ray_tpu.parallel.partition_rules import tree_shardings
    from ray_tpu.train import distributed as dist
    from ray_tpu.train.train_step import (TrainState, make_optimizer,
                                          make_sharded_train_step)

    params = _scaled(llama_init(CFG, jax.random.PRNGKey(7)))
    batch = {"tokens": jnp.concatenate([tokens, tokens])}      # 4 rows
    optimizer = make_optimizer(total_steps=10)

    def loss_fn(cfg):
        return lambda p, b: olmoe_loss_fn(cfg, p, b, with_metrics=True)

    plain = make_sharded_train_step(loss_fn(CFG), optimizer, donate=False,
                                    telemetry=False, has_aux=True)
    _, want = plain(TrainState.create(params, optimizer), batch)

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                ("fsdp", "tensor"))
    state = TrainState.create(params, optimizer)
    specs = dist.fitted_state_specs(state, mesh,
                                    dist.rules_for_model("olmoe"))
    shardings = tree_shardings(mesh, specs)
    state = jax.device_put(state, shardings)
    w_up = state.params["params"]["layer_0"]["moe"]["w_up"]
    assert w_up.sharding.spec == PartitionSpec(None, "fsdp", "tensor")
    step = make_sharded_train_step(
        loss_fn(dataclasses.replace(CFG, mesh=mesh)), optimizer, mesh=mesh,
        donate=False, telemetry=False, state_shardings=shardings,
        batch_sharding=NamedSharding(mesh, PartitionSpec("fsdp")),
        has_aux=True)
    _, got = step(state, jax.device_put(
        batch, NamedSharding(mesh, PartitionSpec("fsdp"))))
    assert set(got) == {"loss", "grad_norm", "ce", "moe_load_balancing",
                        "moe_router_z", "moe_max_load_over_mean"}
    for name in got:
        np.testing.assert_allclose(float(got[name]), float(want[name]),
                                   rtol=1e-5, err_msg=name)
