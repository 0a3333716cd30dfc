"""The training cells' ``correct``: the real check step (train_runner), the
real reference child's arithmetic (check.main) and the real verdict
(check.judge_train) with the tolerances of benchmark/traffic/
pretrain-1k-full.json, at a small size on the CPU.  The program as it is
passes; the program with its attention mathematics broken does not."""
import io
import json
import os
from contextlib import redirect_stdout

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ray_tpu.models.gpt2 as program
from benchmark.harness import check, train_runner
from benchmark.harness.families import family_of
from ray_tpu.train.train_step import (TrainState, make_optimizer,
                                      make_train_step)

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = {"family": "gpt2", "vocab_size": 2048, "n_positions": 256,
          "n_embd": 128, "n_layer": 3, "n_head": 4, "n_inner": 512,
          "layer_norm_epsilon": 1e-05, "compute_dtype": "bfloat16"}
with open(os.path.join(os.path.dirname(HERE), "traffic",
                       "pretrain-1k-full.json")) as f:
    REAL = json.load(f)
TRAFFIC = dict(REAL, global_batch=4, seq_len=256,
               step=dict(REAL["step"], attn_impl="dense", loss_chunk=64))


def _non_causal(cfg, q, k, v):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * q.shape[-1] ** -0.5
    return jnp.einsum("bhqk,bkhd->bqhd",
                      jax.nn.softmax(s, axis=-1).astype(cfg.dtype), v)


def _no_scale(cfg, q, k, v):
    return ATTENTION(cfg, q * q.shape[-1] ** 0.5, k, v)


def _backward_off_by_a_third(cfg, q, k, v):
    """Right forward, wrong backward: dV comes out a third too large."""
    @jax.custom_vjp
    def f(q, k, v):
        return ATTENTION(cfg, q, k, v)

    def fwd(q, k, v):
        return f(q, k, v), (q, k, v)

    def bwd(res, g):
        dq, dk, dv = jax.vjp(lambda *a: ATTENTION(cfg, *a), *res)[1](g)
        return dq, dk, 4.0 / 3.0 * dv
    f.defvjp(fwd, bwd)
    return f(q, k, v)


ATTENTION = program._attention
FAULTS = {
    "non_causal": _non_causal,
    "zeroed": lambda cfg, q, k, v: jnp.zeros_like(q),
    "no_scale": _no_scale,
    "backward_off_by_a_third": _backward_off_by_a_third,
}


def _verdict(tmp_path, monkeypatch, attention=None, seed=2 ** 31 + 11):
    fam = family_of(CONFIG)
    spec = {"seed": seed, "config": CONFIG, "traffic": TRAFFIC,
            "kind": "train", "sizes": fam.sizes(CONFIG),
            "check_file": str(tmp_path / "check_program.npz")}
    cfg = fam.program_config(CONFIG, attn_impl="dense", remat=True)
    optimizer = make_optimizer(**TRAFFIC["step"]["optimizer"])
    state = TrainState.create(fam.init(cfg, jax.random.PRNGKey(seed)),
                              optimizer)
    with monkeypatch.context() as m:
        if attention is not None:
            m.setattr(program, "_attention", attention)
        step = jax.jit(make_train_step(
            lambda p, b: fam.loss(cfg, p, b, loss_chunk=64), optimizer))
        before = state.params
        state, got = train_runner.run_check_step(step, state, spec,
                                                 lambda b: b)
    # the schedule's rate at step 0 is 0: the check step moves no weight
    assert all(bool(jnp.all(a == b)) for a, b in zip(
        jax.tree_util.tree_leaves(before),
        jax.tree_util.tree_leaves(state.params)))
    path = tmp_path / "check_spec.json"
    path.write_text(json.dumps(spec))
    out = io.StringIO()
    with redirect_stdout(out):
        check.main(str(path))
    return check.judge_train(got, json.loads(out.getvalue()),
                             REAL["check"])


def test_the_program_as_it_is_passes(tmp_path, monkeypatch):
    v = _verdict(tmp_path, monkeypatch)
    assert v["problems"] == [], v
    # bf16 against float32: a few hundredths of a leaf's size, far inside
    assert v["apart"]["max_leaf_rel"] < 0.5 * v["limits"]["max_leaf_rel"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_attention_fails_the_check(tmp_path, monkeypatch, fault):
    v = _verdict(tmp_path, monkeypatch, FAULTS[fault])
    print(fault, v["apart"])
    assert any("max_leaf_rel" in p for p in v["problems"]), v
    if fault != "backward_off_by_a_third":      # a fault in every layer
        assert any("median_leaf_rel" in p for p in v["problems"]), v


def test_the_mean_loss_alone_would_not_have_seen_it(tmp_path, monkeypatch):
    """Why the gradients are compared: at random initial weights the mean
    loss moves by about a thousandth when the causal mask goes."""
    v = _verdict(tmp_path, monkeypatch, _non_causal)
    assert v["apart"]["loss"] < 0.005 and v["problems"]
