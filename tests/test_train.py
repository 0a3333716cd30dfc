"""Train stack on the cluster runtime: JaxTrainer end-to-end (GPT-2 tiny
pretrain with session reports + checkpoints), checkpoint manager, resume,
and failure recovery."""

import os
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.train import (Checkpoint, CheckpointConfig, CheckpointManager,
                           FailureConfig, JaxTrainer, RunConfig,
                           ScalingConfig)


@pytest.fixture(scope="module", autouse=True)
def _rt(tmp_path_factory):
    rt = ray_tpu.init(mode="cluster", num_cpus=8)
    yield rt
    ray_tpu.shutdown()


def _gpt2_loop(config):
    """Runs inside a training worker: tiny GPT-2, few steps, reports."""
    import jax
    import jax.numpy as jnp

    from ray_tpu import train
    from ray_tpu.models.gpt2 import GPT2Config, gpt2_init, gpt2_loss_fn
    from ray_tpu.train.train_step import (TrainState, make_optimizer,
                                          make_sharded_train_step)

    cfg = GPT2Config(vocab_size=256, n_layer=1, n_head=2, d_model=64,
                     d_ff=128, max_seq=32, remat=False,
                     dtype=jnp.float32)
    params = gpt2_init(cfg, jax.random.PRNGKey(0))
    opt = make_optimizer(learning_rate=1e-3, warmup_steps=1,
                         total_steps=20)
    state = TrainState.create(params, opt)
    start_step = 0
    ckpt = train.get_checkpoint()
    if ckpt is not None:
        meta = ckpt.load_json("meta")
        start_step = meta["step"]
        state = ckpt.load_pytree("state", state)
    step_fn = make_sharded_train_step(
        lambda p, b: gpt2_loss_fn(cfg, p, b), opt)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, cfg.max_seq + 1),
                                0, cfg.vocab_size)
    for i in range(start_step, config["steps"]):
        state, metrics = step_fn(state, {"tokens": tokens})
        if train.get_world_rank() == 0:
            with train.checkpoint_dir() as d:
                c = Checkpoint(d)
                c.save_pytree("state", state)
                c.save_json("meta", {"step": i + 1})
                train.report({"loss": float(metrics["loss"]),
                              "step": i + 1}, checkpoint=c)
        else:
            train.report({"loss": float(metrics["loss"]),
                          "step": i + 1})
    return float(metrics["loss"])


def test_jax_trainer_single_worker(tmp_path):
    trainer = JaxTrainer(
        _gpt2_loop, train_loop_config={"steps": 4},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="t1", storage_path=str(tmp_path)))
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["step"] == 4
    assert result.checkpoint is not None
    assert os.path.exists(os.path.join(result.checkpoint.path,
                                       "state.msgpack"))
    assert len(result.metrics_history) == 4
    losses = [h["metrics"]["loss"] for h in result.metrics_history]
    assert losses[-1] < losses[0]


def _olmoe_loop(config):
    """Tiny OLMoE (models/llama.py with experts): the step built with
    ``has_aux`` puts the router losses among its metrics."""
    import jax

    from ray_tpu import train
    from ray_tpu.models import MODEL_FAMILIES
    from ray_tpu.train.train_step import (TrainState, make_optimizer,
                                          make_sharded_train_step)

    family = MODEL_FAMILIES["olmoe"]
    cfg = family.tiny()
    opt = make_optimizer(learning_rate=1e-3, warmup_steps=1, total_steps=20)
    state = TrainState.create(family.init(cfg, jax.random.PRNGKey(0)), opt)
    frozen = _frozen_leaves(state.params)
    step_fn = make_sharded_train_step(
        lambda p, b: family.loss(cfg, p, b, with_metrics=True), opt,
        has_aux=True)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0,
                                cfg.vocab_size)
    for i in range(config["steps"]):
        state, metrics = step_fn(state, {"tokens": tokens})
        train.report({k: float(v) for k, v in metrics.items()}
                     | {"step": i + 1})
    return float(metrics["loss"])


def test_jax_trainer_runs_olmoe_and_reports_router_losses(tmp_path):
    result = JaxTrainer(
        _olmoe_loop, train_loop_config={"steps": 4},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="olmoe", storage_path=str(tmp_path))
    ).fit()
    assert result.error is None and result.metrics["step"] == 4
    first, last = (h["metrics"] for h in (result.metrics_history[0],
                                          result.metrics_history[-1]))
    assert last["loss"] < first["loss"]
    # 2 layers: the balanced minimum of the load-balancing loss is 2
    assert first["moe_load_balancing"] >= 2.0 and first["moe_router_z"] > 0
    assert 1.0 <= first["moe_max_load_over_mean"] <= 8.0
    assert first["loss"] > first["ce"]


def _hybrid_loop(config):
    """A tiny hybrid through its registry row, experts 2-5 of 8 held:
    Granite (models/granite.py: state-space layers beside attention, a
    shared expert; the chunked scan under grad) or LFM2 (models/lfm2.py:
    short-conv mixers, dense layers ahead of experts routed by sigmoid
    scores and a frozen selection bias)."""
    import dataclasses

    import jax

    from ray_tpu import train
    from ray_tpu.models import MODEL_FAMILIES
    from ray_tpu.train.train_step import (TrainState, make_optimizer,
                                          make_sharded_train_step)

    family = MODEL_FAMILIES[config["family"]]
    cfg = dataclasses.replace(family.tiny(), first_expert=2,
                              held_experts=4)
    opt = make_optimizer(learning_rate=1e-3, warmup_steps=1, total_steps=20)
    state = TrainState.create(family.init(cfg, jax.random.PRNGKey(0)), opt)
    frozen = _frozen_leaves(state.params)
    step_fn = make_sharded_train_step(
        lambda p, b: family.loss(cfg, p, b), opt)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 30), 0,
                                cfg.vocab_size)
    for i in range(config["steps"]):
        state, metrics = step_fn(state, {"tokens": tokens})
        train.report({"loss": float(metrics["loss"]), "step": i + 1})
    # A leaf no gradient trains is bit-equal after the steps: neither the
    # update nor the optimizer's weight decay moves it.
    after = _frozen_leaves(state.params)
    assert len(after) == config["frozen_leaves"]
    for name, leaf in after.items():
        assert np.array_equal(leaf, frozen[name]), name
        assert np.any(leaf != 0), name
    return float(metrics["loss"])


def _frozen_leaves(params):
    import jax

    return {jax.tree_util.keystr(path): np.array(leaf) for path, leaf
            in jax.tree_util.tree_leaves_with_path(params)
            if path[-1].key == "expert_bias"}


@pytest.mark.parametrize("family", ["granitemoehybrid", "lfm2moe"])
def test_jax_trainer_runs_granite(tmp_path, family):
    result = JaxTrainer(
        _hybrid_loop, train_loop_config={
            "steps": 3, "family": family,
            "frozen_leaves": {"granitemoehybrid": 0, "lfm2moe": 3}[family]},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name=family, storage_path=str(tmp_path))
    ).fit()
    assert result.error is None and result.metrics["step"] == 3
    losses = [h["metrics"]["loss"] for h in result.metrics_history]
    assert losses[-1] < losses[0] < 6.0     # ln(256) = 5.55 at the start


def test_jax_trainer_resume(tmp_path):
    run = RunConfig(name="t2", storage_path=str(tmp_path))
    r1 = JaxTrainer(_gpt2_loop, train_loop_config={"steps": 3},
                    scaling_config=ScalingConfig(num_workers=1),
                    run_config=run).fit()
    assert r1.metrics["step"] == 3
    # Second fit resumes from the persisted checkpoint: only steps 3..5.
    r2 = JaxTrainer(_gpt2_loop, train_loop_config={"steps": 5},
                    scaling_config=ScalingConfig(num_workers=1),
                    run_config=run).fit()
    assert r2.error is None
    steps_run = [h["metrics"]["step"] for h in r2.metrics_history]
    assert steps_run == [4, 5]


def test_multiworker_session_context(tmp_path):
    def loop(config):
        from ray_tpu import train

        train.report({"rank": train.get_world_rank(),
                      "world": train.get_world_size()})
        return train.get_world_rank()

    trainer = JaxTrainer(
        loop, train_loop_config={},
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="t3", storage_path=str(tmp_path)))
    result = trainer.fit()
    assert result.error is None
    assert result.metrics_history[0]["metrics"]["world"] == 2


def test_failure_recovery_restarts_from_checkpoint(tmp_path):
    crash_marker = str(tmp_path / "crashed_once")

    def loop(config):
        import os as _os

        from ray_tpu import train
        from ray_tpu.train import Checkpoint

        start = 0
        ckpt = train.get_checkpoint()
        if ckpt is not None:
            start = ckpt.load_json("meta")["step"]
        for i in range(start, 6):
            if i == 3 and not _os.path.exists(config["marker"]):
                open(config["marker"], "w").close()
                _os._exit(1)  # hard-kill the worker mid-run
            with train.checkpoint_dir() as d:
                c = Checkpoint(d)
                c.save_json("meta", {"step": i + 1})
                train.report({"step": i + 1}, checkpoint=c)
        return start

    trainer = JaxTrainer(
        loop, train_loop_config={"marker": crash_marker},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="t4", storage_path=str(tmp_path),
                             failure_config=FailureConfig(max_failures=1)))
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["step"] == 6
    # The retry resumed from step 3's checkpoint, not from zero.
    steps = [h["metrics"]["step"] for h in result.metrics_history]
    assert steps[0] <= 3 and steps[-1] == 6


def test_checkpoint_manager_top_k(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "run"), num_to_keep=2,
                            score_attribute="acc", score_order="max")
    import os as _os

    for i, acc in enumerate([0.1, 0.9, 0.5]):
        src = tmp_path / f"src{i}"
        src.mkdir()
        (src / "w.txt").write_text(str(acc))
        mgr.register(str(src), {"acc": acc})
    kept = sorted(_os.listdir(tmp_path / "run"))
    assert len(kept) == 2
    scores = sorted(
        float((tmp_path / "run" / d / "w.txt").read_text()) for d in kept)
    assert scores == [0.5, 0.9]
