"""Multi-host training plane (ISSUE 15).

Fast half: the pure topology math behind the gang mesh — axis-size
derivation, the process-contiguous rank→coords invariant (MUST agree
with the sharded checkpoint plane's ``coords_for_rank``), and the
global-batch row slicing — all jax-free.

Slow half (``-m "slow and multihost"``): the acceptance test the ISSUE
pins — a world-2 CPU gang (2 processes x 2 virtual devices, gloo)
trains GPT-2 sharded fsdp x tensor through ``JaxTrainerV2``, per-step
losses match a single-process baseline, a ``PreemptionKiller`` drain
triggers a checkpoint-on-notice sharded save of the DISTRIBUTED
TrainState (each rank its own shards), and the run resumes on world 1
with a different mesh from that checkpoint with ``max_failures=0``
intact.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from ray_tpu.train.distributed import (derive_mesh_shape,
                                       global_batch_slice,
                                       mesh_coords_for_rank)
from ray_tpu.train.sharded_checkpoint import (coords_for_rank,
                                              enumerate_coords)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ===================================================================
# pure topology math (jax-free, tier-1 fast path)
# ===================================================================

def test_derive_mesh_shape_multihost_default_keeps_tensor_local():
    # tensor stays inside a host (ICI-adjacent); fsdp takes the rest.
    assert derive_mesh_shape(2, 2) == {"fsdp": 2, "tensor": 2}
    assert derive_mesh_shape(4, 4) == {"fsdp": 4, "tensor": 4}
    assert derive_mesh_shape(8, 1) == {"fsdp": 8, "tensor": 1}


def test_derive_mesh_shape_single_host_defaults_to_pure_fsdp():
    assert derive_mesh_shape(1, 4) == {"fsdp": 4, "tensor": 1}
    assert derive_mesh_shape(1, 1) == {"fsdp": 1, "tensor": 1}


def test_derive_mesh_shape_pinned_axis_derives_the_other():
    assert derive_mesh_shape(2, 4, tensor=2) == {"fsdp": 4,
                                                 "tensor": 2}
    assert derive_mesh_shape(2, 4, fsdp=2) == {"fsdp": 2, "tensor": 4}
    assert derive_mesh_shape(2, 4, fsdp=8, tensor=1) == {"fsdp": 8,
                                                         "tensor": 1}


def test_derive_mesh_shape_rejects_bad_factorizations():
    with pytest.raises(ValueError):
        derive_mesh_shape(2, 4, tensor=3)      # 3 does not divide 8
    with pytest.raises(ValueError):
        derive_mesh_shape(2, 4, fsdp=3)
    with pytest.raises(ValueError):
        derive_mesh_shape(2, 4, fsdp=2, tensor=2)  # 2x2 != 8
    with pytest.raises(ValueError):
        derive_mesh_shape(0, 4)
    with pytest.raises(ValueError):
        derive_mesh_shape(2, 0)


def test_mesh_coords_agree_with_checkpoint_coords_for_rank():
    # THE invariant: a host-mode sharded save assigns rank r the same
    # mesh coordinates the gang mesh gives its devices, so saves and
    # restores across the two planes always line up.
    shapes = [{"fsdp": 2, "tensor": 2}, {"fsdp": 4, "tensor": 2},
              {"fsdp": 3, "tensor": 1}, {"fsdp": 8, "tensor": 1},
              {"fsdp": 2, "tensor": 4}]
    for shape in shapes:
        for world in (1, 2, 4):
            total = shape["fsdp"] * shape["tensor"]
            if total % world:
                continue
            for rank in range(world):
                assert (mesh_coords_for_rank(shape, rank, world)
                        == coords_for_rank(shape, rank, world)), \
                    (shape, rank, world)


def test_mesh_coords_blocks_partition_the_flattened_mesh():
    shape = {"fsdp": 4, "tensor": 2}
    world = 4
    seen = []
    for rank in range(world):
        block = mesh_coords_for_rank(shape, rank, world)
        assert len(block) == 2  # 8 devices / 4 ranks, contiguous
        seen.extend(block)
    # Union over ranks is the full C-order enumeration, no overlap.
    assert seen == enumerate_coords(shape)


def test_mesh_coords_rejects_bad_rank():
    with pytest.raises(ValueError):
        mesh_coords_for_rank({"fsdp": 2}, 2, 2)
    with pytest.raises(ValueError):
        mesh_coords_for_rank({"fsdp": 2}, -1, 2)


def test_global_batch_slice_covers_batch_in_rank_order():
    shape = {"fsdp": 2, "tensor": 2}
    assert global_batch_slice(8, shape, 0, 2) == (0, 4)
    assert global_batch_slice(8, shape, 1, 2) == (4, 8)


def test_global_batch_slice_replicates_within_an_fsdp_row():
    # tensor spans processes: ranks sharing an fsdp row must present
    # IDENTICAL rows (make_array_from_process_local_data replica rule).
    shape = {"fsdp": 2, "tensor": 2}
    assert global_batch_slice(8, shape, 0, 4) == (0, 4)
    assert global_batch_slice(8, shape, 1, 4) == (0, 4)
    assert global_batch_slice(8, shape, 2, 4) == (4, 8)
    assert global_batch_slice(8, shape, 3, 4) == (4, 8)


def test_global_batch_slice_pure_tensor_mesh_replicates_everywhere():
    shape = {"fsdp": 1, "tensor": 2}
    assert global_batch_slice(8, shape, 0, 2) == (0, 8)
    assert global_batch_slice(8, shape, 1, 2) == (0, 8)


def test_global_batch_slice_validates_divisibility():
    with pytest.raises(ValueError):
        global_batch_slice(7, {"fsdp": 2, "tensor": 1}, 0, 2)
    with pytest.raises(ValueError):
        global_batch_slice(8, {"fsdp": 3, "tensor": 1}, 0, 2)
    with pytest.raises(ValueError):
        global_batch_slice(8, {"fsdp": 2, "tensor": 1}, 2, 2)


# ===================================================================
# acceptance: 2-process CPU gang through JaxTrainerV2 (slow)
# ===================================================================

# The CPU stand-in for a 2-host TPU gang: every worker process gets 2
# virtual devices, and the multi-process CPU backend needs the gloo
# collectives client (xla_group enables it before the first backend
# touch).  Env must be in place before cluster NODE processes spawn;
# ScalingConfig.worker_env re-asserts it per worker attempt.
_JAX_ENV = {
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
}
_ENV = {
    "RT_METRICS_REPORT_PERIOD_S": "0.5",
    "RT_RAYLET_HEARTBEAT_PERIOD_MS": "300",
    "RT_PREEMPTION_GRACE_S": "8",          # SIGTERM drain window
    "RT_RESTART_BACKOFF_BASE_S": "0.3",
    "RT_RESTART_BACKOFF_MAX_S": "1.0",
    "RT_RESTART_BACKOFF_JITTER": "0.25",
    **_JAX_ENV,
}

# One model/optimizer/data recipe shared by the gang loop and the
# single-process baseline: losses are comparable step-for-step only
# because every piece below is deterministic.
_CFG = dict(vocab_size=256, n_layer=1, n_head=2, d_model=64,
            d_ff=128, max_seq=32, remat=False)
_OPT = dict(learning_rate=1e-3, warmup_steps=1, total_steps=100)
_GBS = 8
_STEPS = 14
_BATCH_SEED = 1000


@pytest.fixture(scope="module")
def cluster():
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster

    old = {k: os.environ.get(k) for k in _ENV}
    os.environ.update(_ENV)
    c = Cluster(head_node_args={"num_cpus": 3})
    c.add_node(num_cpus=2)
    ray_tpu.init(address=c.address)
    c.wait_for_nodes()
    yield c
    ray_tpu.shutdown()
    c.shutdown()
    for k, v in old.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def _wait(pred, timeout=120, what="condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        v = pred()
        if v:
            return v
        time.sleep(0.2)
    raise TimeoutError(f"timed out waiting for {what}")


def _dist_loop(config):
    """Each rank: gang bootstrap -> sharded GPT-2 train steps; on an
    agreed drain notice, checkpoint-on-notice saves the DISTRIBUTED
    TrainState (each rank ships only its device shards); a resumed
    attempt (any world) reshard-restores and finishes the budget."""
    import time

    import jax
    import numpy as np

    from ray_tpu import collective as col
    from ray_tpu import train
    from ray_tpu.models.gpt2 import (GPT2Config, gpt2_init,
                                     gpt2_loss_fn)
    from ray_tpu.parallel.partition_rules import tree_shardings
    from ray_tpu.train.train_step import (TrainState, make_optimizer,
                                          make_sharded_train_step)

    world = train.get_world_size()
    rank = train.get_world_rank()
    dm = train.setup_distributed_mesh()
    cfg = GPT2Config(**config["cfg"])
    optimizer = make_optimizer(**config["opt"])
    state = TrainState.create(gpt2_init(cfg, jax.random.PRNGKey(0)),
                              optimizer)
    state, specs = train.shard_train_state(
        state, dm.mesh, train.rules_for_model("gpt2"))
    start, restored_from = 0, 0
    ckpt = train.get_checkpoint()
    if ckpt is not None and ckpt.is_sharded:
        meta = ckpt.manifest_meta()
        start = int(meta["step"]) + 1
        restored_from = int(meta.get("world_size", -1))
        state = train.load_sharded_checkpoint(mesh=dm.mesh,
                                              target=state)
    step_fn = make_sharded_train_step(
        lambda p, b: gpt2_loss_fn(cfg, p, b, loss_chunk=0), optimizer,
        mesh=dm.mesh,
        state_shardings=tree_shardings(dm.mesh, specs),
        batch_sharding=dm.batch_sharding(), telemetry=False)

    gbs = config["gbs"]
    lo, hi = dm.batch_slice(gbs)

    def local_rows(step):
        full = np.random.default_rng(
            config["batch_seed"] + step).integers(
                0, cfg.vocab_size,
                (gbs, cfg.max_seq + 1)).astype(np.int32)
        return {"tokens": full[lo:hi]}

    # Device prefetch under the gang's NamedSharding target: each
    # process ships only its local rows (satellite — no host gather).
    batches = train.iter_device_batches(
        (local_rows(s) for s in range(start, config["steps"])),
        sharding=dm.batch_sharding(), global_batch_size=gbs)

    grp = col.get_group(dm.group_name) if world > 1 else None
    saved_notice = False
    for step, batch in zip(range(start, config["steps"]), batches):
        if grp is not None:
            # Pace the gang phase so the drain notice (killer SIGTERM
            # -> controller broadcast -> 1s-throttled session poll)
            # lands while steps remain; the resumed world runs flat
            # out.
            time.sleep(config.get("pace_s", 0.0))
        if grp is not None and not saved_notice:
            # The interrupt poll is throttled per-rank, so ranks may
            # notice at different steps; the notice save is COLLECTIVE
            # (every rank writes its shard index before rank 0
            # commits), so the gang agrees via an eager allreduce —
            # steps are lockstep, making this race-free.
            flag = np.array(
                [1.0 if train.interrupted() else 0.0])
            if float(grp.allreduce(flag)[0]) > 0:
                saved_notice = True
                with train.checkpoint_on_notice():
                    # `state` holds updates through step-1; a resume
                    # starts at meta step + 1.
                    train.save_sharded_checkpoint(
                        state, step=900000,
                        mesh_axes=dm.axis_sizes,
                        meta={"step": step - 1, "world_size": world},
                        metrics={"notice": True,
                                 "at_step": step - 1},
                        wait_timeout_s=30.0)
        state, metrics = step_fn(state, batch)
        loss = float(np.asarray(metrics["loss"]))  # per-step sync
        train.report({"step": step, "loss": loss, "world": world,
                      "start": start, "restored_from": restored_from,
                      "mesh": dict(dm.axis_sizes)})
        if rank == 0:
            with open(config["progress"], "w") as f:
                f.write(str(step))
    return start


# Single-process oracle on the SAME 2x2 mesh (4 virtual devices, one
# process): the losses a gang run must reproduce step-for-step.
_BASELINE = """
import json, sys
sys.path.insert(0, {repo!r})
import jax
import numpy as np
from ray_tpu.models.gpt2 import GPT2Config, gpt2_init, gpt2_loss_fn
from ray_tpu.parallel.mesh import gang_mesh
from ray_tpu.parallel.partition_rules import tree_shardings
from ray_tpu.train import distributed as dist
from ray_tpu.train.train_step import (TrainState, make_optimizer,
                                      make_sharded_train_step)
cfg = GPT2Config(**{cfg!r})
optimizer = make_optimizer(**{opt!r})
state = TrainState.create(gpt2_init(cfg, jax.random.PRNGKey(0)),
                          optimizer)
mesh = gang_mesh({{"fsdp": 2, "tensor": 2}})
state, specs = dist.shard_train_state(state, mesh,
                                      dist.rules_for_model("gpt2"))
dm = dist.DistributedMesh(mesh=mesh,
                          axis_sizes={{"fsdp": 2, "tensor": 2}})
step_fn = make_sharded_train_step(
    lambda p, b: gpt2_loss_fn(cfg, p, b, loss_chunk=0), optimizer,
    mesh=mesh, state_shardings=tree_shardings(mesh, specs),
    batch_sharding=dm.batch_sharding(), telemetry=False)
losses = []
for step in range({steps}):
    full = np.random.default_rng({seed} + step).integers(
        0, cfg.vocab_size, ({gbs}, cfg.max_seq + 1)).astype(np.int32)
    batch = dist.put_global_batch({{"tokens": full}}, mesh,
                                  global_batch_size={gbs})
    state, metrics = step_fn(state, batch)
    losses.append(float(np.asarray(metrics["loss"])))
print("BASELINE " + json.dumps(losses))
"""


def _baseline_losses():
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    src = _BASELINE.format(repo=REPO, cfg=_CFG, opt=_OPT,
                           steps=_STEPS, gbs=_GBS, seed=_BATCH_SEED)
    r = subprocess.run([sys.executable, "-c", src],
                       capture_output=True, text=True, env=env,
                       timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    for line in r.stdout.splitlines():
        if line.startswith("BASELINE "):
            return json.loads(line.split(" ", 1)[1])
    raise AssertionError(f"no BASELINE line in:\n{r.stdout}")


@pytest.mark.slow
@pytest.mark.multihost
@pytest.mark.chaos
def test_gang_train_matches_baseline_and_resumes_elastically(
        cluster, tmp_path):
    from ray_tpu.testing.chaos import PreemptionKiller
    from ray_tpu.train import (ElasticScalingPolicy, FailurePolicy,
                               JaxTrainerV2, RunConfig, ScalingConfig)
    from ray_tpu.util.checkpoint_fs import verify_checkpoint

    baseline = _baseline_losses()
    progress = str(tmp_path / "progress")
    trainer = JaxTrainerV2(
        _dist_loop,
        train_loop_config={"cfg": _CFG, "opt": _OPT, "gbs": _GBS,
                           "steps": _STEPS,
                           "batch_seed": _BATCH_SEED, "pace_s": 1.0,
                           "progress": progress},
        scaling_config=ScalingConfig(
            num_workers=2, resources_per_worker={"CPU": 2.0},
            placement_strategy="STRICT_SPREAD",
            worker_env=dict(_JAX_ENV)),
        run_config=RunConfig(name="dist_train",
                             storage_path=str(tmp_path)),
        scaling_policy=ElasticScalingPolicy(
            min_workers=1, max_workers=2,
            resources_per_worker={"CPU": 2.0}),
        failure_policy=FailurePolicy(max_failures=0))

    side = {}

    def arm_killer():
        try:
            # Let the gang compile + take a few real steps first.
            _wait(lambda: os.path.exists(progress)
                  and int(open(progress).read() or 0) >= 2,
                  timeout=300, what="gang training progress")
            killer = PreemptionKiller(cluster, interval_s=0.5,
                                      grace_s=6.0, max_kills=1)
            side["killer"] = killer.start()
        except Exception as e:  # surfaced after fit()
            side["error"] = repr(e)

    t = threading.Thread(target=arm_killer, daemon=True)
    t.start()
    result = trainer.fit()
    t.join(timeout=30)
    killer = side.get("killer")
    if killer is not None:
        killer.stop()
    assert "error" not in side, side["error"]
    assert killer is not None and killer.kills, "no preemption fired"

    controller = trainer.controller
    # Finished despite max_failures=0: the preemption was ANNOUNCED.
    assert result.error is None, result.error
    assert controller.announced_failures == 1, (
        controller.attempt_sizes, controller.state_history,
        [h["metrics"] for h in result.metrics_history])
    assert controller.attempt_sizes[0] == 2
    assert controller.attempt_sizes[-1] == 1, controller.attempt_sizes
    resizes = [s for s in controller.state_history
               if s["state"] == "RESIZING"]
    assert any(s.get("ckpt_world") == 2 for s in resizes), resizes

    # The notice save committed a SHARDED checkpoint of the
    # DISTRIBUTED TrainState from world 2 — both ranks contributed.
    notices = [h for h in result.metrics_history
               if h["metrics"].get("notice")]
    assert notices, "no checkpoint-on-notice was reported"
    assert notices[0].get("preempt_ckpt"), notices[0]
    ckpt_dir = notices[0]["checkpoint_path"]
    assert os.path.basename(ckpt_dir) == "checkpoint_900000"
    report = verify_checkpoint(ckpt_dir)
    assert report["ok"] and report["sharded"], report
    assert report["world_size"] == 2
    assert os.path.isdir(os.path.join(ckpt_dir, "shard_1"))
    notice_step = notices[0]["metrics"]["at_step"]

    # The gang phase ran fsdp x tensor over 2 processes; the resumed
    # phase reshard-restored onto a 1-host mesh it never trained on.
    steps = [h["metrics"] for h in result.metrics_history
             if "loss" in h["metrics"]]
    gang = [m for m in steps if m["world"] == 2]
    resumed = [m for m in steps if m["world"] == 1]
    assert gang and resumed, steps
    assert all(m["mesh"] == {"fsdp": 2, "tensor": 2} for m in gang)
    assert all(m["mesh"] == {"fsdp": 2, "tensor": 1}
               for m in resumed)
    assert all(m["restored_from"] == 2 for m in resumed)
    assert all(m["start"] == notice_step + 1 for m in resumed)
    assert max(m["step"] for m in steps) == _STEPS - 1
    # Every step the resumed world re-ran continues from the restored
    # state, so nothing before the notice step reappears.
    assert min(m["step"] for m in resumed) == notice_step + 1

    # THE acceptance bar: per-step losses match the single-process
    # baseline — across both the world-2 mesh and the world-1 resume
    # (restore is bit-exact; the mesh change only reorders float
    # reductions).
    for m in steps:
        want = baseline[m["step"]]
        assert abs(m["loss"] - want) < 2e-3, (m, want)
