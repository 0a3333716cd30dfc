"""Model family tests on the virtual CPU mesh: forward shapes, training
convergence on tiny configs, sharded DP x TP x SP training step, llama
GQA/RoPE path, and the graft entry points."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.gpt2 import (GPT2, GPT2Config, gpt2_init,
                                 gpt2_loss_fn)
from ray_tpu.models.layers import chunked_xent
from ray_tpu.models.llama import (Llama, LlamaConfig, llama_init,
                                  llama_loss_fn)
from ray_tpu.train.train_step import (TrainState, make_optimizer,
                                      make_sharded_train_step)


def _batch(cfg, batch=4, key=0):
    return {"tokens": jax.random.randint(
        jax.random.PRNGKey(key), (batch, cfg.max_seq + 1), 0,
        cfg.vocab_size, jnp.int32)}


def test_gpt2_forward_shape():
    cfg = GPT2Config.tiny()
    params = gpt2_init(cfg, jax.random.PRNGKey(0))
    logits = GPT2(cfg).apply(params, jnp.zeros((2, 16), jnp.int32))
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_gpt2_loss_decreases():
    cfg = dataclasses.replace(GPT2Config.tiny(), remat=False,
                              dtype=jnp.float32)
    params = gpt2_init(cfg, jax.random.PRNGKey(0))
    opt = make_optimizer(learning_rate=1e-3, warmup_steps=2,
                         total_steps=30)
    state = TrainState.create(params, opt)
    step = make_sharded_train_step(
        lambda p, b: gpt2_loss_fn(cfg, p, b), opt)
    batch = _batch(cfg)
    losses = []
    for _ in range(10):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] - 0.3, losses


def test_gpt2_sharded_training_step():
    from ray_tpu.parallel import gang_mesh
    from ray_tpu.parallel.partition_rules import tree_shardings
    from ray_tpu.train import distributed as dist

    mesh = gang_mesh({"data": 2, "seq": 2, "tensor": 2})
    cfg = dataclasses.replace(GPT2Config.tiny(), mesh=mesh,
                              attn_impl="ring", dtype=jnp.float32)
    opt = make_optimizer(total_steps=10)
    # Placed as the trainer places it: the family's partition rules,
    # fitted to this mesh.
    state, specs = dist.shard_train_state(
        TrainState.create(gpt2_init(cfg, jax.random.PRNGKey(0)), opt),
        mesh, dist.rules_for_model("gpt2"))
    c_attn = state.params["params"]["h_0"]["c_attn"]["kernel"]
    assert c_attn.sharding.spec == jax.sharding.PartitionSpec(
        None, "tensor")
    step = make_sharded_train_step(
        lambda p, b: gpt2_loss_fn(cfg, p, b), opt, mesh=mesh,
        state_shardings=tree_shardings(mesh, specs),
        batch_sharding=dist.batch_sharding(mesh))
    tokens = jax.device_put(_batch(cfg)["tokens"],
                            dist.batch_sharding(mesh))
    state, metrics = step(state, {"tokens": tokens})
    assert np.isfinite(float(metrics["loss"]))
    # Ring attention must equal the dense path.
    dense_cfg = dataclasses.replace(cfg, attn_impl="dense", mesh=None)
    dense_loss = gpt2_loss_fn(dense_cfg, state.params, _batch(cfg))
    ring_loss = gpt2_loss_fn(cfg, state.params, _batch(cfg))
    np.testing.assert_allclose(float(dense_loss), float(ring_loss),
                               rtol=2e-4)


# A vocabulary no other dimension of the tiny model equals (d_ff is 512,
# c_attn's output 384), so a shape that holds it is the loss's.
XENT_CFG = dataclasses.replace(GPT2Config.tiny(), vocab_size=320,
                               remat=False, dtype=jnp.float32)


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("cotangent", ["one", "three", "value_only"])
def test_chunked_xent_agrees_with_whole_logits(chunk, cotangent):
    """The chunked loss makes its gradient in its forward scan and its
    backward rule only scales it by the cotangent: in float32 the loss,
    dx (through every parameter below ln_f) and d wte are the
    whole-logits path's to rounding, whatever the cotangent; a call
    that is not differentiated gives the same value."""
    cfg = XENT_CFG
    params = gpt2_init(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, batch=2)
    scale = 3.0 if cotangent == "three" else 1.0

    def loss(p, loss_chunk):
        return scale * gpt2_loss_fn(cfg, p, batch, loss_chunk=loss_chunk)

    if cotangent == "value_only":
        got = jax.jit(loss, static_argnums=1)(params, chunk)
        np.testing.assert_allclose(float(got), float(loss(params, 0)),
                                   rtol=1e-6)
        return
    (got, got_grads), (want, want_grads) = (
        jax.value_and_grad(loss)(params, c) for c in (chunk, 0))
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    got_grads = dict(jax.tree_util.tree_leaves_with_path(got_grads))
    for path, b in jax.tree_util.tree_leaves_with_path(want_grads):
        np.testing.assert_allclose(
            np.asarray(got_grads[path]), np.asarray(b), rtol=2e-4,
            atol=1e-6 * scale, err_msg=jax.tree_util.keystr(path))


def _recomputing_xent_grads(x, wte, targets, chunk):
    """The backward pass this loss had while it kept the log-sum-exps
    and RECOMPUTED each chunk's logits (a fourth vocabulary-sized
    matmul a chunk), written out for a cotangent of 1: what today's
    gradients are held to."""
    b, t, d = x.shape
    n = t // chunk
    xs = jnp.moveaxis(x.reshape(b, n, chunk, d), 1, 0)
    ts = jnp.moveaxis(targets.reshape(b, n, chunk), 1, 0)
    scale = jnp.float32(1.0) / (b * t)

    def lse_of(_, xc):
        logits = jnp.einsum("bcd,vd->bcv", xc, wte,
                            preferred_element_type=jnp.float32)
        return None, jax.nn.logsumexp(logits, axis=-1)

    _, lses = jax.lax.scan(lse_of, None, xs)

    def body(dw, xt):
        xc, tc, lse = xt
        logits = jnp.einsum("bcd,vd->bcv", xc, wte,
                            preferred_element_type=jnp.float32)
        p = jnp.exp(logits - lse[..., None])
        onehot = jax.nn.one_hot(tc, wte.shape[0], dtype=p.dtype)
        dl = ((p - onehot) * scale).astype(x.dtype)
        dx_c = jnp.einsum("bcv,vd->bcd", dl, wte)
        dw = dw + jnp.einsum("bcv,bcd->vd", dl, xc,
                             preferred_element_type=jnp.float32)
        return dw, dx_c

    dw, dxs = jax.lax.scan(body, jnp.zeros(wte.shape, jnp.float32),
                           (xs, ts, lses))
    return jnp.moveaxis(dxs, 0, 1).reshape(b, t, d), dw.astype(wte.dtype)


@pytest.mark.parametrize("chunk", [16, 64])
def test_chunked_xent_bf16_gradients_are_the_recomputing_backwards(chunk):
    """With bf16 activations (the training cells' dtype) the gradients
    are, to the bit, those of the backward pass that recomputed the
    logits: the same three einsums on the same numbers in the same
    order over the chunks."""
    v, d = XENT_CFG.vocab_size, XENT_CFG.d_model
    kx, kw, kt = jax.random.split(jax.random.PRNGKey(7), 3)
    x = jax.random.normal(kx, (2, 128, d), jnp.float32).astype(jnp.bfloat16)
    wte = (0.02 * jax.random.normal(kw, (v, d))).astype(jnp.bfloat16)
    targets = jax.random.randint(kt, (2, 128), 0, v, jnp.int32)
    dx, dw = jax.jit(jax.grad(chunked_xent, argnums=(0, 1)),
                     static_argnums=3)(x, wte, targets, chunk)
    want_dx, want_dw = jax.jit(_recomputing_xent_grads, static_argnums=3)(
        x, wte, targets, chunk)
    assert dx.dtype == dw.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(dx, np.float32),
                                  np.asarray(want_dx, np.float32))
    np.testing.assert_array_equal(np.asarray(dw, np.float32),
                                  np.asarray(want_dw, np.float32))


def _vocab_dots(jaxpr, v, scans=()):
    """The enclosing scans (by identity) of every ``dot_general`` of
    ``jaxpr``, nested jaxprs included, that has ``v`` as a dimension of
    an operand or of its result."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and any(
                v in var.aval.shape for var in (*eqn.invars, *eqn.outvars)):
            found.append(scans)
        inner = scans + (id(eqn),) if eqn.primitive.name == "scan" else scans
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _vocab_dots(sub, v, inner)
    return found


@pytest.mark.parametrize("differentiated,matmuls", [(True, 3), (False, 1)])
def test_chunked_xent_makes_each_chunks_logits_once(differentiated,
                                                    matmuls):
    """Under jax.grad the step holds THREE vocabulary-sized matmuls
    (logits, dx, d wte), all in one scan: nothing recomputes the logits.
    A value-only call holds the one."""
    cfg = XENT_CFG
    params = gpt2_init(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, batch=2)

    def loss(p):
        return gpt2_loss_fn(cfg, p, batch, loss_chunk=32)

    jaxpr = jax.make_jaxpr(jax.grad(loss) if differentiated else loss)(
        params).jaxpr
    dots = _vocab_dots(jaxpr, cfg.vocab_size)
    assert len(dots) == matmuls, dots
    assert len(set(dots)) == 1 and len(dots[0]) == 1, dots


# rows of a step -> sha256[:16] of the lowered text (``lower().as_text()``,
# no debug info) of the one-chip GPT-2 124M training step at the two
# one-chip cells' shapes (flash, remat, loss_chunk 256, AdamW, donated
# state), read from PR 51's tree, the parent of the PR that moved the
# chunked loss to models/layers.py and taught it a mesh.
GPT2_124M_STEP_PARENT_TEXT = {32: "6e14fd881a23fe60", 16: "341f20972642216d"}


@pytest.mark.parametrize("rows", sorted(GPT2_124M_STEP_PARENT_TEXT))
def test_one_chip_train_step_lowers_to_the_parents_text(rows):
    """With ``cfg.mesh`` None the loss takes the chunked scan on the one
    device, as before the tokens could be cut over a mesh: the whole step
    of ``train-gpt2-124m`` (32 rows) and ``train-gpt2-124m-b16`` lowers to
    the text it lowered to before, letter for letter."""
    import hashlib

    cfg = GPT2Config(attn_impl="flash", remat=True)
    optimizer = make_optimizer(total_steps=100)
    state = jax.eval_shape(lambda: TrainState.create(
        gpt2_init(cfg, jax.random.PRNGKey(0)), optimizer))
    step = make_sharded_train_step(
        lambda p, b: gpt2_loss_fn(cfg, p, b, loss_chunk=256), optimizer,
        telemetry=False)
    text = step.lower(state, {"tokens": jax.ShapeDtypeStruct(
        (rows, cfg.max_seq + 1), jnp.int32)}).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        GPT2_124M_STEP_PARENT_TEXT[rows]


def test_llama_flash_agrees_with_dense():
    """Both blocks share one attention core (models/attention.py), so
    the Llama block has the flash kernel GPT-2 trains with (interpret
    mode here), GQA heads repeated before it; loss and gradients agree
    with dense."""
    cfg = dataclasses.replace(LlamaConfig.tiny(), attn_impl="flash",
                              remat=False, dtype=jnp.float32)
    assert cfg.n_kv_head < cfg.n_head
    params = llama_init(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, batch=2)
    dense = dataclasses.replace(cfg, attn_impl="dense")
    loss_f, grads_f = jax.value_and_grad(
        lambda p: llama_loss_fn(cfg, p, batch))(params)
    loss_d, grads_d = jax.value_and_grad(
        lambda p: llama_loss_fn(dense, p, batch))(params)
    np.testing.assert_allclose(float(loss_f), float(loss_d), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(grads_f),
                    jax.tree_util.tree_leaves(grads_d)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-4)


def test_llama_forward_and_loss():
    cfg = LlamaConfig.tiny()
    params = llama_init(cfg, jax.random.PRNGKey(0))
    logits = Llama(cfg).apply(params, jnp.zeros((2, 16), jnp.int32))
    assert logits.shape == (2, 16, cfg.vocab_size)
    loss = llama_loss_fn(cfg, params, _batch(cfg, batch=2))
    assert np.isfinite(float(loss))
    # Untrained loss should be near ln(vocab).
    assert abs(float(loss) - np.log(cfg.vocab_size)) < 1.0
