"""The training cells' ``correct`` can see the flash kernels' walk (PR 34):
the benchmark's own check step (``train_runner.run_check_step``), reference
(``check.main``) and verdict (``check.judge_train``) with the tolerances of
``benchmark/traffic/pretrain-1k-full.json``, at a small size on the CPU with
``attn_impl="flash"`` and a sequence long enough for the diagonal block to
be walked in sub-blocks.  The kernels as they are pass; with the walk broken
on purpose they do not, nor with the two heads of a 128-lane block mixed up
(PR 36: the cell's heads of 64 are packed two to a program).  (``benchmark/tests/test_train_check.py`` holds the
same for the dense path and fails at collection since PR 28.)"""
import importlib
import io
import json
import os
from contextlib import redirect_stdout

import jax
import jax.numpy as jnp
import pytest

from benchmark.harness import check, train_runner
from benchmark.harness.families import family_of
from ray_tpu.train.train_step import (TrainState, make_optimizer,
                                      make_train_step)

flash = importlib.import_module("ray_tpu.ops.flash_attention")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = {"family": "gpt2", "vocab_size": 2048, "n_positions": 512,
          "n_embd": 128, "n_layer": 2, "n_head": 2, "n_inner": 512,
          "layer_norm_epsilon": 1e-05, "compute_dtype": "bfloat16"}
with open(os.path.join(ROOT, "benchmark", "traffic",
                       "pretrain-1k-full.json")) as f:
    REAL = json.load(f)
TRAFFIC = dict(REAL, global_batch=2, seq_len=512,
               step=dict(REAL["step"], attn_impl="flash", loss_chunk=128))


def _mask_dropped(monkeypatch):
    """The pairs on the diagonal attend their whole square."""
    scores = flash._scores
    monkeypatch.setattr(flash, "_scores",
                        lambda q, k, scale, mask: scores(q, k, scale, None))


def _pairs_dropped(monkeypatch):
    """A q sub-block attends the pair on the diagonal alone."""
    monkeypatch.setattr(
        flash.CausalSchedule, "strips",
        lambda self: [(slice(i * self.sub, (i + 1) * self.sub),) * 2
                      for i in range(1 + max(p[0] for p in self.pairs))])


def _backward_keeps_the_whole_square(monkeypatch):
    """Right forward; the backward kernels forget the mask."""
    p_ds = flash._p_ds
    monkeypatch.setattr(
        flash, "_p_ds",
        lambda q, k, v, do, lse, delta, scale, mask:
        p_ds(q, k, v, do, lse, delta, scale, None))


def _halves_exchanged(monkeypatch):
    """Each head's ``out`` lands in the lanes of the other head of its
    block (the saved lse, and so the backward's P, are right)."""
    forward = flash._flash_forward

    def exchanged(ops, *, call):
        out, lse = forward(ops, call=call)
        b, t, _ = out.shape
        halves = out.reshape(b, t, call.n, call.hpp, call.d)
        return halves[:, :, :, ::-1].reshape(out.shape), lse

    monkeypatch.setattr(flash, "_flash_forward", exchanged)


def _head_mask_left_out(monkeypatch):
    """q (and dO) keep the other head's lanes: a head's scores are the
    sum of both heads' of its block."""
    monkeypatch.setattr(flash, "_head", lambda x, a, call: x)


FAULTS = {"mask_dropped": _mask_dropped, "pairs_dropped": _pairs_dropped,
          "backward_keeps_the_whole_square":
          _backward_keeps_the_whole_square,
          "halves_exchanged": _halves_exchanged,
          "head_mask_left_out": _head_mask_left_out}


def _verdict(tmp_path, monkeypatch, fault=None, seed=2 ** 31 + 11):
    fam = family_of(CONFIG)
    spec = {"seed": seed, "config": CONFIG, "traffic": TRAFFIC,
            "kind": "train", "sizes": fam.sizes(CONFIG),
            "check_file": str(tmp_path / "check_program.npz")}
    cfg = fam.program_config(CONFIG, attn_impl="flash", remat=True)
    assert flash.causal_schedule(512, 512, 512, 64).sub     # it is walked
    assert flash._packs(CONFIG["n_head"], 64) == 2    # two heads a program
    optimizer = make_optimizer(**TRAFFIC["step"]["optimizer"])
    state = TrainState.create(fam.init(cfg, jax.random.PRNGKey(seed)),
                              optimizer)
    with monkeypatch.context() as m:
        if fault is not None:
            jax.clear_caches()      # the kernels are jitted: trace anew
            FAULTS[fault](m)
        step = jax.jit(make_train_step(
            lambda p, b: fam.loss(cfg, p, b, loss_chunk=128), optimizer))
        state, got = train_runner.run_check_step(step, state, spec,
                                                 lambda b: b)
    if fault is not None:
        jax.clear_caches()
    path = tmp_path / "check_spec.json"
    path.write_text(json.dumps(spec))
    out = io.StringIO()
    with redirect_stdout(out):
        check.main(str(path))
    return check.judge_train(got, json.loads(out.getvalue()),
                             REAL["check"])


def test_the_walked_kernels_pass_the_cells_check(tmp_path, monkeypatch):
    v = _verdict(tmp_path, monkeypatch)
    assert v["problems"] == [], v
    assert v["apart"]["max_leaf_rel"] < 0.5 * v["limits"]["max_leaf_rel"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_walk_fails_the_cells_check(tmp_path, monkeypatch, fault):
    v = _verdict(tmp_path, monkeypatch, fault)
    print(fault, v["apart"])
    assert any("max_leaf_rel" in p for p in v["problems"]), v
