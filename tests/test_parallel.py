"""Parallelism module on the 8-device virtual CPU mesh: mesh/sharding
rules, ring attention vs dense reference (values AND gradients), Ulysses,
pipeline parallelism vs sequential execution."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.parallel import (AXIS_ORDER, gang_mesh, pipeline_apply,
                              ring_attention, ulysses_attention)
from ray_tpu.parallel.sharding import (DEFAULT_RULES, logical_sharding,
                                       logical_spec)
from jax.sharding import PartitionSpec as P
from jax import shard_map


def dense_attention(q, k, v, causal=True):
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * d ** -0.5
    if causal:
        t = q.shape[1]
        mask = np.tril(np.ones((t, t), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(p.dtype)).astype(q.dtype)


def test_gang_mesh_axes():
    mesh = gang_mesh({"data": 2, "tensor": 4})
    assert mesh.shape["data"] == 2 and mesh.shape["tensor"] == 4
    assert mesh.axis_names == ("data", "tensor")
    # Process-major C order: the rightmost axis varies fastest.
    assert [d.id for d in mesh.devices.ravel()] == sorted(
        d.id for d in jax.devices())


@pytest.mark.parametrize("axes,why", [
    ({"data": 2, "model": 4}, "not in the vocabulary"),
    ({"data": 3}, "needs 3 devices"),
], ids=["unknown_axis", "wrong_device_count"])
def test_gang_mesh_refuses(axes, why):
    """One vocabulary (AXIS_ORDER: the names the partition rules and the
    activation table are written in) and every device accounted for."""
    assert "model" not in AXIS_ORDER
    with pytest.raises(ValueError, match=why):
        gang_mesh(axes)


def test_sharding_rules_prune():
    mesh = gang_mesh({"data": 8})
    sh = logical_sharding(mesh, ("batch", "embed"))
    assert sh.spec == P("data")
    sh2 = logical_sharding(mesh, ("batch", "mlp"))  # no tensor axis
    assert sh2.spec == P("data")
    # Fitted to the array as well: an axis that does not divide its dim
    # leaves the dim whole (25 heads, a vocabulary of 50257).
    mesh = gang_mesh({"fsdp": 2, "seq": 1, "tensor": 4})
    heads = ("batch", "seq", "heads", None)
    assert logical_spec(mesh, heads) == P("fsdp", None, "tensor")
    assert logical_spec(mesh, heads, (4, 8, 25, 64)) == P("fsdp")
    assert logical_spec(mesh, heads, (3, 8, 12, 64)) == P(None, None,
                                                          "tensor")


@pytest.mark.parametrize("fsdp,tensor", [(1, 1), (2, 2), (4, 1)])
def test_batch_sharding_on_the_cells_meshes(fsdp, tensor):
    """The table's ``batch`` row, fitted to the trainer's fsdp x tensor
    mesh, is the batch over ``fsdp``: what ``global_batch_slice`` and
    the benchmark's compile tool assume."""
    from jax.sharding import NamedSharding

    from ray_tpu.train.distributed import DistributedMesh

    axes = {"fsdp": fsdp, "tensor": tensor}
    mesh = gang_mesh(axes, jax.devices()[:fsdp * tensor])
    got = DistributedMesh(mesh=mesh, axis_sizes=axes).batch_sharding()
    assert got.is_equivalent_to(NamedSharding(mesh, P("fsdp")), 2)


# The output dimension of each family's first attention, MLP and
# embedding weight: (path in the tiny preset's params, dim, logical name).
_OUTPUT_DIMS = {
    "gpt2": [("h_0/c_attn/kernel", 1, "heads"),
             ("h_0/mlp_in/kernel", 1, "mlp"), ("wte", 0, "vocab")],
    "llama": [("layer_0/wq/kernel", 1, "heads"),
              ("layer_0/w_gate/kernel", 1, "mlp"), ("embed", 0, "vocab")],
    "olmoe": [("layer_0/wq/kernel", 1, "heads"),
              ("layer_0/moe/w_gate", 2, "mlp"), ("embed", 0, "vocab")],
    "lfm2moe": [("layer_2/attn/wq/kernel", 1, "heads"),
                ("layer_0/w_gate/kernel", 1, "mlp"),
                ("layer_2/moe/w_gate", 2, "mlp"), ("embed", 0, "vocab")],
}


@pytest.mark.parametrize("family", sorted(_OUTPUT_DIMS))
def test_rules_and_table_agree(family):
    """The weights' description (a family's partition rules) and the
    activations' (the table) are two tables that must tell one story: a
    weight's output dimension lies on the mesh axis the table gives the
    activation it produces."""
    from ray_tpu.models import MODEL_FAMILIES
    from ray_tpu.parallel.partition_rules import match_partition_rules
    from ray_tpu.train.distributed import rules_for_model

    fam = MODEL_FAMILIES[family]
    cfg = fam.tiny()
    params = jax.eval_shape(lambda: fam.init(cfg, jax.random.PRNGKey(0)))
    specs = match_partition_rules(rules_for_model(family), params)
    for path, dim, logical in _OUTPUT_DIMS[family]:
        spec = specs["params"]
        for key in path.split("/"):
            spec = spec[key]
        assert spec[dim] == DEFAULT_RULES[logical], (path, spec, logical)


@pytest.mark.parametrize("impl", ["flash", "lax"])
@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_dense(causal, impl):
    mesh = gang_mesh({"data": 2, "seq": 4})
    b, t, h, d = 2, 32, 4, 16
    key = jax.random.PRNGKey(0)
    q, k, v = jax.random.normal(key, (3, b, t, h, d), jnp.float32)

    spec = P(("data",), "seq", None, None)
    ring = shard_map(
        functools.partial(ring_attention, causal=causal, impl=impl),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    out = jax.jit(ring)(q, k, v)
    ref = dense_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("impl", ["flash", "lax"])
def test_ring_attention_gradients(impl):
    mesh = gang_mesh({"data": 2, "seq": 4})
    b, t, h, d = 1, 16, 2, 8
    q, k, v = jax.random.normal(jax.random.PRNGKey(1), (3, b, t, h, d))

    spec = P(None, "seq", None, None)
    ring = shard_map(functools.partial(ring_attention, causal=True,
                                       impl=impl),
                     mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)

    def loss_ring(q, k, v):
        return jnp.sum(ring(q, k, v) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(dense_attention(q, k, v, True) ** 2)

    g1 = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=3e-5, rtol=3e-5)


def test_ulysses_matches_dense():
    mesh = gang_mesh({"data": 2, "seq": 4})
    b, t, h, d = 2, 32, 8, 16  # heads divisible by seq axis
    q, k, v = jax.random.normal(jax.random.PRNGKey(2), (3, b, t, h, d))

    spec = P(None, "seq", None, None)
    uly = shard_map(functools.partial(ulysses_attention, causal=True),
                    mesh=mesh, in_specs=(spec, spec, spec),
                    out_specs=spec, check_vma=False)
    out = jax.jit(uly)(q, k, v)
    ref = dense_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_pipeline_matches_sequential():
    mesh = gang_mesh({"data": 2, "pipeline": 4})
    s, b, dim = 4, 8, 16
    keys = jax.random.split(jax.random.PRNGKey(3), s)
    ws = jnp.stack([jax.random.normal(k, (dim, dim)) * 0.3 for k in keys])
    x = jax.random.normal(jax.random.PRNGKey(4), (b, dim))

    def stage_fn(w, h):
        return jnp.tanh(h @ w)

    piped = shard_map(
        functools.partial(pipeline_apply, stage_fn, num_microbatches=4),
        mesh=mesh, in_specs=(P("pipeline"), P(None)),
        out_specs=P(None), check_vma=False)
    out = jax.jit(lambda ws, x: piped(ws, x))(ws, x)

    ref = x
    for i in range(s):
        ref = stage_fn(ws[i], ref)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_pipeline_gradients_flow():
    mesh = gang_mesh({"data": 2, "pipeline": 4})
    s, b, dim = 4, 8, 8
    ws = jax.random.normal(jax.random.PRNGKey(5), (s, dim, dim)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(6), (b, dim))

    def stage_fn(w, h):
        return jnp.tanh(h @ w)

    piped = shard_map(
        functools.partial(pipeline_apply, stage_fn, num_microbatches=2),
        mesh=mesh, in_specs=(P("pipeline"), P(None)),
        out_specs=P(None), check_vma=False)

    def loss(ws):
        return jnp.sum(piped(ws, x) ** 2)

    def ref_loss(ws):
        h = x
        for i in range(s):
            h = stage_fn(ws[i], h)
        return jnp.sum(h ** 2)

    g = jax.jit(jax.grad(loss))(ws)
    g_ref = jax.grad(ref_loss)(ws)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               atol=2e-5, rtol=2e-5)


# One sharded train step per mesh layout over the 8-device CPU mesh,
# built and placed as the cells' trainer does it (gang_mesh, the
# family's partition rules fitted to the mesh, state born sharded, the
# step pinned to those shardings): DP x SP x TP with ring attention,
# DP x EP x TP with MoE blocks, and a dcn axis stacked over data x
# tensor.
_MESH_LAYOUTS = {
    "dp_sp_tp": ({"data": 2, "seq": 2, "tensor": 2},
                 dict(vocab_size=512, n_layer=2, n_head=4, d_model=128,
                      d_ff=256, max_seq=64, attn_impl="ring"), 4),
    "dp_ep_tp": ({"data": 2, "expert": 2, "tensor": 2},
                 dict(vocab_size=256, n_layer=2, n_head=4, d_model=64,
                      d_ff=128, max_seq=32, moe_num_experts=4,
                      moe_every=2), 4),
    "dcn_dp_tp": ({"dcn": 2, "data": 2, "tensor": 2},
                  dict(vocab_size=256, n_layer=2, n_head=4, d_model=64,
                       d_ff=128, max_seq=32), 8),
}


def sharded_step(cfg, mesh, optimizer, family="gpt2", loss_chunk=0):
    """(state, step, batch_sharding) on ``mesh``, as
    benchmark/harness/train_runner.py makes them."""
    from ray_tpu.models import MODEL_FAMILIES
    from ray_tpu.parallel.partition_rules import tree_shardings
    from ray_tpu.train import distributed as dist
    from ray_tpu.train.train_step import (TrainState,
                                          make_sharded_train_step)

    fam = MODEL_FAMILIES[family]

    def create(key):
        return TrainState.create(fam.init(cfg, key), optimizer)

    key = jax.random.PRNGKey(0)
    specs = dist.fitted_state_specs(jax.eval_shape(create, key), mesh,
                                    dist.rules_for_model(family))
    shardings = tree_shardings(mesh, specs)
    state = jax.jit(create, out_shardings=shardings)(key)
    batch_sharding = dist.batch_sharding(mesh)
    step = make_sharded_train_step(
        lambda p, b: fam.loss(cfg, p, b, loss_chunk=loss_chunk),
        optimizer, mesh=mesh, state_shardings=shardings,
        batch_sharding=batch_sharding)
    return state, step, batch_sharding


@pytest.mark.parametrize("layout", sorted(_MESH_LAYOUTS))
def test_sharded_train_step_on_meshspec_axes(layout):
    from ray_tpu.models.gpt2 import GPT2Config
    from ray_tpu.train.train_step import make_optimizer

    axes, model_kw, batch = _MESH_LAYOUTS[layout]
    mesh = gang_mesh(axes)
    cfg = GPT2Config(mesh=mesh, remat=True, **model_kw)
    state, step, batch_sharding = sharded_step(
        cfg, mesh, make_optimizer(total_steps=10, warmup_steps=2))
    if layout == "dp_ep_tp":
        w_in = state.params["params"]["h_1"]["moe_mlp"]["w_in"]
        assert w_in.sharding.spec == P("expert", None, "tensor")
    tokens = jax.device_put(
        jnp.zeros((batch, cfg.max_seq + 1), jnp.int32), batch_sharding)
    # The batch lies where the model constrains its activations to lie.
    assert batch_sharding.spec == logical_spec(mesh, ("batch",))
    state, metrics = step(state, {"tokens": tokens})
    assert np.isfinite(float(metrics["loss"])), metrics
    # The executable that ran is the ahead-of-time one, compiled for
    # this mesh.
    assert step.compiled() is not None


def test_sharded_flash_step_with_heads_the_tensor_axis_does_not_divide():
    """Three heads over tensor=2: the flash kernel's shard_map spec is
    fitted to the array like every other activation's (the heads stay
    whole, as GPT-2's vocabulary does), so the step compiles, and it
    computes what the unsharded step computes."""
    import dataclasses

    from ray_tpu.models.gpt2 import GPT2Config
    from ray_tpu.train.train_step import make_optimizer

    mesh = gang_mesh({"fsdp": 2, "tensor": 2}, jax.devices()[:4])
    plain = GPT2Config(vocab_size=256, n_layer=2, n_head=3, d_model=96,
                       d_ff=192, max_seq=128, attn_impl="flash",
                       dtype=jnp.float32)
    optimizer = make_optimizer(total_steps=10, warmup_steps=2)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 129), 0, 256,
                                jnp.int32)
    losses = {}
    for name, m in (("one", gang_mesh({"fsdp": 1}, jax.devices()[:1])),
                    ("four", mesh)):
        cfg = dataclasses.replace(plain, mesh=m if m.size > 1 else None)
        state, step, batch_sharding = sharded_step(cfg, m, optimizer)
        _, metrics = step(
            state, {"tokens": jax.device_put(tokens, batch_sharding)})
        losses[name] = float(metrics["loss"])
    np.testing.assert_allclose(losses["four"], losses["one"], rtol=1e-5)


# ---------------------------------------------------------------------
# c_attn across a mesh (PR 40): the projection's WEIGHT goes to the heads
# and its output is born on the ``tensor`` axis by head; no activation
# crosses the axis for the split into q, k and v.

def _qkv_cfg(heads, **kw):
    from ray_tpu.models.gpt2 import GPT2Config

    # T = 128 is no weight's dimension here (64, 192 | 256, 3x, 320, 512),
    # so an operand that has it is an activation.
    return GPT2Config(vocab_size=320, n_layer=2, n_head=heads,
                      d_model=64 * heads, d_ff=512, max_seq=128,
                      attn_impl="flash", dtype=jnp.float32, **kw)


def _placed(params, mesh):
    """``params`` where the GPT-2 rules, fitted to ``mesh``, put them,
    and those specs."""
    from ray_tpu.parallel.partition_rules import tree_shardings
    from ray_tpu.train import distributed as dist
    from ray_tpu.train.train_step import TrainState, make_optimizer

    optimizer = make_optimizer(total_steps=10, warmup_steps=2)
    state = jax.eval_shape(lambda p: TrainState.create(p, optimizer), params)
    specs = dist.fitted_state_specs(state, mesh,
                                    dist.rules_for_model("gpt2")).params
    return jax.device_put(params, tree_shardings(mesh, specs)), specs


_BLOCK_COLLECTIVE = re.compile(
    r"= (?P<type>\([^=]*?\)|\S+) (?P<op>all-to-all|collective-permute)"
    r"(?:-start)?\(.*op_name=\"[^\"]*/h_\d+/")


def _moved_in_blocks(text, t):
    """(opcode, result type) of every all-to-all and collective-permute
    under a block ``h_<i>`` of a compiled step, and those among them
    whose operand has the sequence dimension: activations."""
    found = [(m.group("op"), m.group("type"))
             for m in map(_BLOCK_COLLECTIVE.search, text.splitlines()) if m]
    return found, [f for f in found
                   if re.search(rf"[\[,]{t}[,\]]", f[1])]


@functools.lru_cache(maxsize=None)
def _qkv_step(remat, vocab_size=320, loss_chunk=0):
    """The training step of ``_qkv_cfg(4)`` (4 heads of 64) over fsdp=2 x
    tensor=2, run once: its config, its loss, its compiled text and its
    row in xprof's table of programs."""
    import dataclasses

    from ray_tpu.util import xprof

    from ray_tpu.train.train_step import make_optimizer

    mesh = gang_mesh({"fsdp": 2, "tensor": 2}, jax.devices()[:4])
    cfg = dataclasses.replace(_qkv_cfg(4, remat=remat, mesh=mesh),
                              vocab_size=vocab_size)
    state, step, batch_sharding = sharded_step(
        cfg, mesh, make_optimizer(total_steps=10, warmup_steps=2),
        loss_chunk=loss_chunk)
    tokens = jax.device_put(jnp.zeros((4, cfg.max_seq + 1), jnp.int32),
                            batch_sharding)
    _, metrics = step(state, {"tokens": tokens})
    return (cfg, float(metrics["loss"]), step.compiled().as_text(),
            xprof.local_programs()["train_step"])


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_sharded_step_moves_no_activation_for_the_qkv_split(remat):
    """fsdp=2 x tensor=2, 4 heads of 64: the compiled training step holds
    no all-to-all under a block and no collective-permute of an
    activation there (the parent's held two permutes of [B, T, H*D] a
    layer and pass under ``attn.qkv/split``)."""
    cfg, loss, text, _ = _qkv_step(remat)
    assert np.isfinite(loss)
    assert "all-reduce" in text             # it IS the sharded program
    found, activations = _moved_in_blocks(text, cfg.max_seq)
    assert not activations, activations
    assert not [f for f in found if f[0] == "all-to-all"], found
    # The scan sees what it is there to see: an activation's permute.
    sample = ('%cp = (f32[2,128,256]{2,1,0}, u32[]) collective-permute-start('
              '%x), metadata={op_name="jit(step)/h_0/attn.qkv/split"}')
    assert _moved_in_blocks(sample, 128)[1]


# ---------------------------------------------------------------------
# What a block keeps across its remat boundary (PR 50): its input and the
# residual stream after the attention sublayer, so the backward pass makes
# neither ``c_proj``'s all-reduce nor a matmul again.

_INSTRUCTION = re.compile(
    r"= (?P<type>\([^=]*?\)|\S+) (?P<op>[\w-]+)\("
    r".*op_name=\"(?P<name>[^\"]*)\"")


def block_sums_and_recomputed(text, t, d):
    """Of a compiled step: how many all-reduce OPERANDS of an activation
    (``[.., t, d]``; a combined all-reduce's tuple counts each) lie under
    each block ``h_<i>``, and the ``op_name`` of every all-reduce, ``dot``
    and ``convolution`` under ``rematted_computation/``."""
    sums, recomputed = {}, []
    # The TPU's compiler makes a sum whose reader takes a part of the rows
    # (the last block's ``mlp_out`` before the split loss) a reduce-
    # scatter: a fusion that calls an ``%all-reduce-scatter`` computation,
    # here one whose input is an activation.
    scatters = set(re.findall(
        rf"^%(all-reduce-scatter[\w.]*) \([^)]*\[[\d,]*\b{t},{d}\]",
        text, re.M))
    for m in filter(None, map(_INSTRUCTION.search, text.splitlines())):
        op, name = m.group("op").removesuffix("-start"), m.group("name")
        called = re.search(r"calls=%(all-reduce-scatter[\w.]*)", m.string)
        scattered = bool(op == "fusion" and called
                         and called.group(1) in scatters)
        if op not in ("all-reduce", "dot", "convolution") and not scattered:
            continue
        if "rematted_computation/" in name:
            recomputed.append((op, name))
        layer = re.search(r"/(h_\d+)/", name)
        if (op == "all-reduce" or scattered) and layer:
            n = 1 if scattered else len(re.findall(
                rf"\[[\d,]*\b{t},{d}\]", m.group("type")))
            sums[layer.group(1)] = sums.get(layer.group(1), 0) + n
    return sums, recomputed


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_sharded_step_sums_four_activations_a_block(remat):
    """fsdp=2 x tensor=2: a block's compiled step holds the FOUR
    activation-sized sums tensor parallelism needs (forward after
    ``c_proj`` and ``mlp_out``, backward the input cotangents of
    ``c_attn`` and ``mlp_in``) and, remat'd, makes no sum again and no
    matmul behind the residual it kept (the parent remade ``c_proj``'s
    sum, its matmul and ``mlp_in``'s a layer)."""
    cfg, _, text, _ = _qkv_step(remat)
    sums, recomputed = block_sums_and_recomputed(text, cfg.max_seq,
                                                 cfg.d_model)
    assert sums == {f"h_{i}": 4 for i in range(cfg.n_layer)}, sums
    # What is made again lies BEFORE the kept residual: ``c_attn`` and the
    # kernel, which the CPU's compiler does not merge with their first
    # forward (interpreted here, the kernel is a loop of matmuls; the
    # chip's executable holds none: tests/test_tpu_compile.py).
    assert not [r for r in recomputed
                if r[0] == "all-reduce" or not re.search(
                    r"/h_\d+/attn\.(qkv|core)/", r[1])], recomputed
    # The scan sees what it is there to see: a combined pair under a
    # recomputed block counts two operands, and is listed.
    sample = ('%ar = (f32[2,128,256]{2,1,0}, f32[2,128,256]{2,1,0}) '
              'all-reduce-start(%a, %b), metadata={op_name="jit(step)/'
              'checkpoint/rematted_computation/h_1/attn.out/c_proj/dot"}')
    assert block_sums_and_recomputed(sample, 128, 256) == (
        {"h_1": 2}, [("all-reduce", "jit(step)/checkpoint/"
                      "rematted_computation/h_1/attn.out/c_proj/dot")])
    # ... and a sum the chip's compiler made a reduce-scatter counts one.
    # (one of a weight's gradient, ``.1``, does not)
    sample = ('%all-reduce-scatter (input: f32[2,128,256]) -> f32[18,8,128] {\n'
              '%all-reduce-scatter.1 (input.1: f32[256,512]) -> f32[128,512] {\n'
              '%fusion.5 = f32[18,8,128]{2,1,0} fusion(%f), kind=kCustom, '
              'calls=%all-reduce-scatter, metadata={op_name="jit(step)/'
              'jvp(GPT2)/h_1/mlp/mlp_out/dot_general"}\n'
              '%fusion.7 = f32[128,512]{1,0} fusion(%g), kind=kCustom, '
              'calls=%all-reduce-scatter.1, metadata={op_name="jit(step)/'
              'transpose(jvp(GPT2))/h_1/mlp/mlp_in/dot_general"}')
    assert block_sums_and_recomputed(sample, 128, 256) == ({"h_1": 1}, [])


def test_remat_changes_what_is_recomputed_not_what_is_computed():
    """Loss and every gradient leaf of the remat'd program across fsdp=2 x
    tensor=2 are the ``remat=False`` program's, and so are the bytes it
    sums over the ``tensor`` axis, as the step's own counter reads them
    (``xprof.local_programs()["train_step"]["collectives"]``)."""
    import dataclasses

    from ray_tpu.models.gpt2 import gpt2_init, gpt2_loss_fn
    from ray_tpu.train import distributed as dist
    from ray_tpu.util import xprof

    mesh = gang_mesh({"fsdp": 2, "tensor": 2}, jax.devices()[:4])
    plain = _qkv_cfg(4, remat=False, mesh=mesh)
    placed, _ = _placed(gpt2_init(plain, jax.random.PRNGKey(0)), mesh)
    batch = jax.device_put({"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (4, plain.max_seq + 1), 0, plain.vocab_size,
        jnp.int32)}, dist.batch_sharding(mesh))

    def run(cfg):
        compiled = jax.jit(jax.value_and_grad(
            lambda p, b: gpt2_loss_fn(cfg, p, b, loss_chunk=0))).lower(
                placed, batch).compile()
        summed = xprof.harvest_compiled(
            compiled, dist.mesh_axis_sizes(mesh))["collectives"]
        return compiled(placed, batch), \
            summed["tensor"]["by_op"]["all-reduce"]

    (want_loss, want), want_summed = run(plain)
    (got_loss, got), got_summed = run(dataclasses.replace(plain, remat=True))
    assert got_summed == want_summed > 0
    assert float(got_loss) == float(want_loss)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
    for path, g in jax.tree_util.tree_leaves_with_path(got):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(flat_want[path]), rtol=1e-5, atol=1e-7,
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("heads", [4, 3], ids=["heads4", "heads3"])
@pytest.mark.parametrize("tensor", [1, 2], ids=["tensor1", "tensor2"])
def test_sharded_qkv_gives_the_unsharded_loss_and_gradients(tensor, heads):
    """Loss and every gradient leaf across fsdp=2 x tensor are the
    ``mesh=None`` program's to float32 tolerance: heads the axis divides
    (each shard's own), and three heads over tensor=2, which stay whole
    on every shard."""
    import dataclasses

    from ray_tpu.models.gpt2 import gpt2_init, gpt2_loss_fn
    from ray_tpu.parallel.sharding import logical_shards

    mesh = gang_mesh({"fsdp": 2, "tensor": tensor},
                     jax.devices()[:2 * tensor])
    plain = _qkv_cfg(heads)
    cfg = dataclasses.replace(plain, mesh=mesh)
    assert logical_shards(mesh, "heads", heads) == \
        (tensor if heads % tensor == 0 else 1)
    params = gpt2_init(plain, jax.random.PRNGKey(0))
    # c_attn's bias is zero at init: give it values, so that the bias's
    # view is held to the plain one too.
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x + 0.1 * jax.random.normal(
            jax.random.PRNGKey(7), x.shape, x.dtype)
        if "c_attn" in jax.tree_util.keystr(path) else x, params)
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (4, plain.max_seq + 1), 0, plain.vocab_size,
        jnp.int32)}
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: gpt2_loss_fn(plain, p, batch, loss_chunk=0)))(params)
    from ray_tpu.train import distributed as dist

    placed, _ = _placed(params, mesh)
    got_loss, got = jax.jit(jax.value_and_grad(
        lambda p, b: gpt2_loss_fn(cfg, p, b, loss_chunk=0)))(
            placed, jax.device_put(batch, dist.batch_sharding(mesh)))
    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=1e-5)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
    for path, g in jax.tree_util.tree_leaves_with_path(got):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(flat_want[path]), rtol=2e-4, atol=2e-6,
            err_msg=jax.tree_util.keystr(path))


def test_c_attn_is_stored_as_dense_stores_it_and_restores_its_checkpoint(
        tmp_path):
    """The stored tree is the one ``nn.Dense`` made (names, shapes,
    dtypes, the very values a seed draws), the GPT-2 rules fit it as
    before, and a sharded checkpoint of such a tree restores into this
    one bit for bit and computes the unsharded loss."""
    import dataclasses

    import flax.linen as nn

    from ray_tpu.models.gpt2 import gpt2_init, gpt2_loss_fn
    from ray_tpu.train import distributed as dist
    from ray_tpu.train.sharded_checkpoint import (load_sharded,
                                                  read_manifest,
                                                  save_sharded)

    class ParentBlock(nn.Module):       # c_attn as nn.Dense, under h_0
        @nn.compact
        def __call__(self, x):
            return nn.Dense(3 * x.shape[-1], name="c_attn",
                            kernel_init=nn.initializers.normal(0.02))(x)

    class ParentModel(nn.Module):
        @nn.compact
        def __call__(self, x):
            return ParentBlock(name="h_0")(x)

    plain = _qkv_cfg(4)
    d, key = plain.d_model, jax.random.PRNGKey(3)
    params = gpt2_init(plain, key)
    ours = params["params"]["h_0"]["c_attn"]
    dense = ParentModel().init(key, jnp.zeros((1, 8, d)))[
        "params"]["h_0"]["c_attn"]
    assert {k: (v.shape, v.dtype) for k, v in ours.items()} == \
        {k: (v.shape, v.dtype) for k, v in dense.items()} == \
        {"kernel": ((d, 3 * d), jnp.float32), "bias": ((3 * d,), jnp.float32)}
    for k in dense:
        assert np.array_equal(np.asarray(ours[k]), np.asarray(dense[k])), k

    mesh = gang_mesh({"fsdp": 2, "tensor": 2}, jax.devices()[:4])
    placed, specs = _placed(params, mesh)
    assert specs["params"]["h_0"]["c_attn"] == \
        {"kernel": P("fsdp", "tensor"), "bias": P()}
    assert specs["params"]["h_0"]["c_proj"]["kernel"] == P("tensor", "fsdp")

    path = str(tmp_path / "checkpoint_000001")
    assert save_sharded(path, placed)["committed"]
    kernel = read_manifest(path)["leaves"]["params/h_0/c_attn/kernel"]
    assert (kernel["shape"], kernel["spec"]) == \
        ([d, 3 * d], ["fsdp", "tensor"])
    restored = load_sharded(path, mesh=mesh, specs=specs,
                            target=jax.eval_shape(lambda: params))
    for (name, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(restored),
            jax.tree_util.tree_leaves_with_path(params)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (4, plain.max_seq + 1), 0, plain.vocab_size,
        jnp.int32)}
    cfg = dataclasses.replace(plain, mesh=mesh)
    got = jax.jit(lambda p, b: gpt2_loss_fn(cfg, p, b, loss_chunk=0))(
        restored, jax.device_put(batch, dist.batch_sharding(mesh)))
    want = jax.jit(lambda p: gpt2_loss_fn(plain, p, batch, loss_chunk=0))(
        params)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)


# ---------------------------------------------------------------------
# The head and its loss under a mesh (PR 52): the one-scan chunked loss
# with the TOKENS cut over the batch's axes and over ``tensor`` too, each
# chip against the whole tied ``wte``; an odd vocabulary left GSPMD the
# same whole float32 logits on both ``tensor`` shards.

ODD_VOCAB = 321         # no axis of the mesh divides it, as 50,257


@pytest.mark.parametrize("axes,kw,shape,chunk,want", [
    (None, {}, (4, 128), 32, ("chunked", 32, (), (), 1)),
    (None, {}, (4, 128), 0, ("whole", 0, (), (), 1)),
    (None, {}, (4, 128), 128, ("whole", 0, (), (), 1)),
    (None, {}, (4, 128), 48, ("whole", 0, (), (), 1)),
    ({"fsdp": 2, "tensor": 2}, {}, (4, 128), 32,
     ("chunked", 32, ("fsdp", "tensor"), (), 4)),
    ({"fsdp": 2, "tensor": 2}, {}, (2, 128), 32,
     ("chunked", 16, ("fsdp",), ("tensor",), 4)),
    ({"fsdp": 2, "tensor": 2}, {}, (6, 128), 32,
     ("chunked", 16, ("fsdp",), ("tensor",), 4)),
    ({"fsdp": 2, "tensor": 2}, {}, (2, 128), 1, ("whole", 0, (), (), 1)),
    ({"fsdp": 2, "tensor": 2}, {}, (3, 128), 32,
     ("chunked", 16, (), ("tensor",), 2)),
    ({"fsdp": 2, "tensor": 2}, {}, (3, 128), 1, ("whole", 0, (), (), 1)),
    ({"fsdp": 4}, {}, (4, 128), 32, ("chunked", 32, ("fsdp",), (), 4)),
    ({"fsdp": 4}, {}, (3, 128), 32, ("whole", 0, (), (), 1)),
    ({"dcn": 2, "data": 2, "tensor": 2}, {}, (8, 128), 32,
     ("chunked", 32, ("dcn", "data", "tensor"), (), 8)),
    ({"data": 2, "seq": 2, "tensor": 2}, {}, (4, 128), 32,
     ("whole", 0, (), (), 1)),
    ({"data": 2, "expert": 2, "tensor": 2},
     dict(moe_num_experts=4, moe_every=2), (4, 128), 32,
     ("whole", 0, (), (), 1)),
    (None, dict(moe_num_experts=4, moe_every=2), (4, 128), 32,
     ("whole", 0, (), (), 1)),
])
def test_loss_layout_names_the_path_by_the_mesh_and_the_shapes(
        axes, kw, shape, chunk, want):
    """The one question the program asks: whole logits or the chunked
    scan, and over which axes the tokens are cut.  Rows before positions;
    a sequence axis, an MoE config, a ``T`` that is no whole number of
    chunks, or a ``tensor`` axis that divides neither: whole logits."""
    from ray_tpu.models.gpt2 import loss_layout

    mesh = axes and gang_mesh(axes, jax.devices()[:int(np.prod(
        list(axes.values())))])
    assert tuple(loss_layout(_qkv_cfg(4, mesh=mesh, **kw), shape,
                             chunk)) == want


@functools.lru_cache(maxsize=None)
def _loss_and_grads(rows, tensor, remat, loss_chunk):
    """Loss and gradients of the odd-vocabulary ``_qkv_cfg(4)`` on seeded
    tokens: across fsdp=2 x ``tensor`` (0: one device, no mesh)."""
    import dataclasses

    from ray_tpu.models.gpt2 import gpt2_init, gpt2_loss_fn
    from ray_tpu.train import distributed as dist

    plain = dataclasses.replace(_qkv_cfg(4, remat=remat),
                                vocab_size=ODD_VOCAB)
    params = gpt2_init(plain, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (rows, plain.max_seq + 1), 0, ODD_VOCAB,
        jnp.int32)}
    cfg = plain
    if tensor:
        mesh = gang_mesh({"fsdp": 2, "tensor": tensor},
                         jax.devices()[:2 * tensor])
        cfg = dataclasses.replace(plain, mesh=mesh)
        params, _ = _placed(params, mesh)
        batch = jax.device_put(batch, dist.batch_sharding(mesh))
    return jax.jit(jax.value_and_grad(
        lambda p, b: gpt2_loss_fn(cfg, p, b, loss_chunk=loss_chunk)))(
            params, batch)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("rows,chunk,cut", [
    (4, 32, "rows"), (2, 32, "positions"), (2, 1, "neither")])
def test_split_chunked_loss_is_the_whole_logits_loss(rows, chunk, cut,
                                                     remat):
    """fsdp=2 x tensor=2, an odd vocabulary: with the tokens cut over
    both axes (two rows a batch shard: one a chip; one row: half of each
    chunk's positions) loss and every gradient leaf are the whole-logits
    path's and the one-device chunked loss's; where ``tensor`` divides
    neither the rows nor a chunk, the program IS the whole-logits one."""
    import dataclasses

    from ray_tpu.models.gpt2 import gpt2_init, gpt2_loss_fn, loss_layout

    mesh = gang_mesh({"fsdp": 2, "tensor": 2}, jax.devices()[:4])
    cfg = dataclasses.replace(_qkv_cfg(4, remat=remat, mesh=mesh),
                              vocab_size=ODD_VOCAB)
    layout = loss_layout(cfg, (rows, cfg.max_seq), chunk)
    assert (layout.path, layout.rows, layout.positions, layout.shards) == {
        "rows": ("chunked", ("fsdp", "tensor"), (), 4),
        "positions": ("chunked", ("fsdp",), ("tensor",), 4),
        "neither": ("whole", (), (), 1)}[cut]
    if cut == "neither":
        tokens = jax.ShapeDtypeStruct((rows, cfg.max_seq + 1), jnp.int32)
        params = jax.eval_shape(
            lambda: gpt2_init(cfg, jax.random.PRNGKey(0)))
        texts = [jax.jit(jax.value_and_grad(
            lambda p, b: gpt2_loss_fn(cfg, p, b, loss_chunk=c))).lower(
                params, {"tokens": tokens}).as_text() for c in (chunk, 0)]
        assert texts[0] == texts[1]
        return
    got_loss, got = _loss_and_grads(rows, 2, remat, chunk)
    for name, (want_loss, want) in (
            ("whole logits", _loss_and_grads(rows, 2, remat, 0)),
            ("one device", _loss_and_grads(rows, 0, False, chunk))):
        np.testing.assert_allclose(float(got_loss), float(want_loss),
                                   rtol=1e-5, err_msg=name)
        flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
        for path, g in jax.tree_util.tree_leaves_with_path(got):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(flat_want[path]), rtol=2e-4,
                atol=2e-6, err_msg=name + jax.tree_util.keystr(path))


_COMPUTATION = re.compile(r"^(?:ENTRY )?%?(?P<name>[\w.-]+) \(.*\) -> .* \{$")
_CALLED = re.compile(r"(?:body|condition|calls|to_apply)=%?([\w.-]+)")
_RESULT = re.compile(r"^\s*(?:ROOT )?%?(?P<name>[\w.-]+) = "
                     r"(?P<type>\(.*?\)|\S+) (?P<op>[\w-]+)\((?P<args>.*)")
_ARRAY = re.compile(r"\b[a-z]+\d*\[([\d,]+)\]")


def _dims(type_str):
    """The shapes (tuples of ints) of every array a type string names."""
    return [tuple(int(n) for n in dims.split(","))
            for dims in _ARRAY.findall(type_str)]


def _instructions(text):
    """(computation, name, type, opcode, the rest of the line) of every
    instruction of a compiled text, and the computations that run inside
    a ``while``: the loops' bodies and conditions and all they call."""
    found, calls, roots, comp = [], {}, set(), None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            comp = head.group("name")
            continue
        m = _RESULT.match(line)
        if not m:
            continue
        found.append((comp, m.group("name"), m.group("type"), m.group("op"),
                      m.group("args")))
        called = _CALLED.findall(line)
        calls.setdefault(comp, set()).update(called)
        if m.group("op") == "while":
            roots.update(called)
    looped, todo = set(), list(roots)
    while todo:
        c = todo.pop()
        if c not in looped:
            looped.add(c)
            todo += calls.get(c, ())
    return found, looped


def vocab_arrays(text, v):
    """The shape of every array of a compiled text that has ``v`` as a
    dimension (a tuple's elements each)."""
    found, _ = _instructions(text)
    return {dims for _, _, type_str, _, _ in found
            for dims in _dims(type_str) if v in dims}


def vocab_matmuls(text, v, d):
    """(in a loop?, tokens) of every ``dot`` / ``convolution`` of a
    compiled text whose result or an operand has the vocabulary ``v`` as
    a dimension: the tokens are the other dimensions of its array that is
    no ``[v, d]`` weight (the logits, or their cotangent)."""
    found, looped = _instructions(text)
    types = {name: type_str for _, name, type_str, _, _ in found}
    out = []
    for comp, _, type_str, op, args in found:
        if op not in ("dot", "convolution"):
            continue
        operands = re.findall(r"%([\w.-]+)", args.split("), ")[0])
        arrays = [dims for t in [type_str] + [types.get(o, "")
                                              for o in operands]
                  for dims in _dims(t) if v in dims]
        tokens = [int(np.prod(dims)) // v for dims in arrays
                  if sorted(dims) != sorted((v, d))]
        if arrays:
            out.append((comp in looped, min(tokens, default=0)))
    return out


def summed_across_chips(text, shape):
    """For every all-reduce / reduce-scatter OPERAND of ``shape`` in a
    compiled text (a combined one's tuple counts each): whether the
    reduction runs inside a ``while``."""
    found, looped = _instructions(text)
    return [comp in looped for comp, _, type_str, op, _ in found
            if op.removesuffix("-start") in ("all-reduce", "reduce-scatter")
            for dims in _dims(type_str) if dims == tuple(shape)]


_LOOPED_SUM = """\
%body.1 (p: (s32[], f32[321,256])) -> (s32[], f32[321,256]) {
  %dw = f32[321,256]{1,0} dot(%dl, %xc), lhs_contracting_dims={0}
  %ar = f32[321,256]{1,0} all-reduce(%dw), replica_groups={{0,1,2,3}}, to_apply=%add
  %dl = f32[2,128,321]{2,1,0} dot(%x, %wte), lhs_contracting_dims={2}
}
ENTRY %main.2 (a: f32[8]) -> f32[] {
  %w = (s32[], f32[321,256]{1,0}) while(%t), condition=%cond.3, body=%body.1
  %ars = (f32[256]{0}, f32[321,256]{1,0}) all-reduce-start(%g, %dw2), to_apply=%add
}
"""


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_sharded_step_makes_a_quarter_of_the_logits_a_chip(remat):
    """The compiled step of fsdp=2 x tensor=2 with an odd vocabulary and
    ``loss_chunk``: no array of a batch shard's ``[rows, T, V]`` logits
    (nor any past a chip's quarter of a chunk), the three vocabulary-sized
    matmuls in the loss's loop on a quarter of a chunk's tokens each, ``d
    wte`` summed across the chips ONCE and outside that loop, and a
    block's four activation sums as before."""
    chunk = 32
    cfg, loss, text, row = _qkv_step(remat, ODD_VOCAB, chunk)
    assert np.isfinite(loss)
    # The step's own harvest says which loss it compiled, beside its
    # collectives by kind.
    assert row["loss"] == {"path": "chunked", "token_shards": 4,
                           "chunk": chunk}
    assert row["collective_counts"]["all-reduce"] > 0
    v, d, t = cfg.vocab_size, cfg.d_model, cfg.max_seq
    rows = 4                            # _qkv_step's batch: 2 an fsdp shard
    quarter = rows * chunk // 4
    logits = {dims for dims in vocab_arrays(text, v)
              if sorted(dims) not in (sorted((v, d)), sorted((v, d // 2)))}
    assert logits and all(np.prod(dims) // v <= quarter
                          for dims in logits), logits
    assert (rows // 2, t, v) not in logits
    matmuls = vocab_matmuls(text, v, d)
    assert matmuls == [(True, quarter)] * 3, matmuls
    # (the embedding's own gradient is summed as [V, D / fsdp])
    assert summed_across_chips(text, (v, d)) == [False]
    sums, _ = block_sums_and_recomputed(text, t, d)
    assert sums == {f"h_{i}": 4 for i in range(cfg.n_layer)}, sums
    # The whole-logits program of the same step is what these scans are
    # there to see: a batch shard's logits, matmuls outside any loop.
    _, _, whole, row = _qkv_step(remat, ODD_VOCAB, 0)
    assert row["loss"] == {"path": "whole", "token_shards": 1, "chunk": 0}
    assert (rows // 2, t, v) in vocab_arrays(whole, v)
    assert whole.count(" while(") < text.count(" while(")
    assert not any(looped for looped, _ in vocab_matmuls(whole, v, d))
    # ... and a synthetic text: a sum of [V, D] inside a loop's body is
    # told from one outside, a combined sum counts its [V, D] operand.
    assert summed_across_chips(_LOOPED_SUM, (v, d)) == [True, False]
    assert (2, 128, v) in vocab_arrays(_LOOPED_SUM, v)
    # (``d wte``'s tokens are read off its operand, the logits' cotangent)
    assert vocab_matmuls(_LOOPED_SUM, v, d) == [(True, 256), (True, 256)]
