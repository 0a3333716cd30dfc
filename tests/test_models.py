"""Model family tests on the virtual CPU mesh: forward shapes, training
convergence on tiny configs, sharded DP x TP x SP training step, llama
GQA/RoPE path, and the graft entry points."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.gpt2 import GPT2, GPT2Config, gpt2_init, gpt2_loss_fn
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
