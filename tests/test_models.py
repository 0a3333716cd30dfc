"""Model family tests on the virtual CPU mesh: forward shapes, training
convergence on tiny configs, sharded DP x TP x SP training step, llama
GQA/RoPE path, and the graft entry points."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.gpt2 import (GPT2, GPT2Config, gpt2_init, gpt2_loss_fn,
                                 gpt2_param_axes)
from ray_tpu.models.llama import (Llama, LlamaConfig, llama_init,
                                  llama_loss_fn)
from ray_tpu.train.train_step import (TrainState, make_optimizer,
                                      make_sharded_train_step, shard_state)


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
    from ray_tpu.parallel import MeshSpec, create_mesh
    from ray_tpu.parallel.sharding import ShardingRules, logical_sharding

    mesh = create_mesh(MeshSpec(data=2, seq=2, tensor=2))
    rules = ShardingRules()
    cfg = dataclasses.replace(GPT2Config.tiny(), mesh=mesh, rules=rules,
                              attn_impl="ring", dtype=jnp.float32)
    params = gpt2_init(cfg, jax.random.PRNGKey(0))
    opt = make_optimizer(total_steps=10)
    state = shard_state(TrainState.create(params, opt), mesh,
                        gpt2_param_axes, rules)
    step = make_sharded_train_step(
        lambda p, b: gpt2_loss_fn(cfg, p, b), opt)
    tokens = jax.device_put(
        _batch(cfg)["tokens"],
        logical_sharding(mesh, ("batch", None), rules))
    state, metrics = step(state, {"tokens": tokens})
    assert np.isfinite(float(metrics["loss"]))
    # Ring attention must equal the dense path.
    dense_cfg = dataclasses.replace(cfg, attn_impl="dense", mesh=None)
    dense_loss = gpt2_loss_fn(dense_cfg, state.params, _batch(cfg))
    ring_loss = gpt2_loss_fn(cfg, state.params, _batch(cfg))
    np.testing.assert_allclose(float(dense_loss), float(ring_loss),
                               rtol=2e-4)


def test_llama_forward_and_loss():
    cfg = LlamaConfig.tiny()
    params = llama_init(cfg, jax.random.PRNGKey(0))
    logits = Llama(cfg).apply(params, jnp.zeros((2, 16), jnp.int32))
    assert logits.shape == (2, 16, cfg.vocab_size)
    loss = llama_loss_fn(cfg, params, _batch(cfg, batch=2))
    assert np.isfinite(float(loss))
    # Untrained loss should be near ln(vocab).
    assert abs(float(loss) - np.log(cfg.vocab_size)) < 1.0
