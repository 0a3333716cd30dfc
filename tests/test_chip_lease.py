"""Chip ownership of worker processes, without a chip: what a worker is
spawned with on a node that has chips, what a lease exports, and that
nothing but a lease can make a process open the chip."""

import os
import subprocess
import sys
import textwrap

import pytest

import ray_tpu
from ray_tpu.core import chip_lease

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_spawn_env_keeps_a_worker_off_the_chip():
    env = {"JAX_PLATFORMS": "tpu,cpu", "TPU_VISIBLE_CHIPS": "0,1",
           "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,2,1", "OTHER": "x"}
    chip_lease.guard_spawn_env(env, node_chips=4)
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env[chip_lease.GUARD_ENV] == "tpu,cpu"
    # Nothing of a launcher's own lease leaks into the worker.
    assert "TPU_VISIBLE_CHIPS" not in env
    assert "TPU_CHIPS_PER_PROCESS_BOUNDS" not in env
    assert env["OTHER"] == "x"

    unset = {}
    chip_lease.guard_spawn_env(unset, node_chips=1)
    assert unset == {"JAX_PLATFORMS": "cpu", chip_lease.GUARD_ENV: ""}

    cpu_node = {"JAX_PLATFORMS": "tpu,cpu"}
    chip_lease.guard_spawn_env(cpu_node, node_chips=0)
    assert cpu_node == {"JAX_PLATFORMS": "tpu,cpu"}


@pytest.mark.parametrize("chips,bounds", [
    ([0], "1,1,1"), ([3], "1,1,1"), ([0, 1], "1,2,1"),
    ([0, 1, 2, 3], "2,2,1")])
def test_lease_env_bounds_libtpu_accepts(chips, bounds):
    """1,1,1 / 1,2,1 / 2,2,1 are what libtpu 0.0.34 accepted on a v5e
    2x2 host (PR 21's probe; 2,1,1 for two chips hung)."""
    assert chip_lease.lease_env(chips) == {
        "TPU_VISIBLE_CHIPS": ",".join(map(str, chips)),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": bounds,
        "TPU_PROCESS_BOUNDS": "1,1,1"}


def test_lease_of_unknown_size_raises():
    with pytest.raises(chip_lease.ChipLeaseError, match="3 chips"):
        chip_lease.lease_env([0, 1, 2])


@pytest.fixture
def fake_environ(monkeypatch):
    """apply_lease on a private environment, with the backend reported
    as not yet started (this test process has one)."""
    env = {chip_lease.GUARD_ENV: "cpu", "JAX_PLATFORMS": "cpu"}
    monkeypatch.setattr(os, "environ", env)
    monkeypatch.setattr(chip_lease, "backend_initialized", lambda: False)
    return env


def test_second_lease_replaces_the_first(fake_environ):
    chip_lease.apply_lease([0, 1, 2, 3])
    assert fake_environ["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "2,2,1"
    assert chip_lease.GUARD_ENV not in fake_environ
    chip_lease.apply_lease([2])
    assert fake_environ["TPU_VISIBLE_CHIPS"] == "2"
    assert fake_environ["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert fake_environ["TPU_PROCESS_BOUNDS"] == "1,1,1"


def test_lease_lifts_the_guard_to_what_the_node_runs_under(fake_environ):
    fake_environ[chip_lease.GUARD_ENV] = ""     # node: JAX picks
    fake_environ["TPU_VISIBLE_CHIPS"] = "9"     # stale, must not survive
    import jax

    before = jax.config.jax_platforms
    try:
        chip_lease.apply_lease([0])
        assert "JAX_PLATFORMS" not in fake_environ
        assert fake_environ["TPU_VISIBLE_CHIPS"] == "0"
        # jax was imported long ago: the config follows the lease too.
        assert jax.config.jax_platforms is None
    finally:
        jax.config.update("jax_platforms", before)


def test_no_lease_changes_nothing(fake_environ):
    before = dict(fake_environ)
    chip_lease.apply_lease([])
    assert fake_environ == before


def test_lease_after_backend_initialised_names_the_cause(monkeypatch):
    import jax

    jax.devices()                       # this process has a backend now
    monkeypatch.setattr(os, "environ", dict(os.environ))
    with pytest.raises(chip_lease.ChipLeaseError,
                       match="after it had initialised a JAX backend"):
        chip_lease.apply_lease([0])


def test_metrics_tick_never_starts_a_backend():
    """publish_device_memory() in a process that imported jax but never
    used it: 0 series, and the backend is still not initialised."""
    script = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {REPO!r})
        import jax
        from jax._src import xla_bridge
        from ray_tpu.util import chips, xprof
        assert not chips.backend_initialized()
        assert chips.local_device_kind() is None
        assert xprof.publish_device_memory() == 0
        assert not xla_bridge._backends, xla_bridge._backends
        jax.devices()
        assert chips.backend_initialized()
        assert chips.local_device_kind() == "cpu"
        xprof.publish_device_memory()    # CPU reports no stats: fine
        print("TICK_OK")
    """)
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=120,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert "TICK_OK" in out.stdout, out.stderr[-3000:]


def _worker_view():
    import sys as _sys

    from ray_tpu.util.chips import backend_initialized

    keys = ("JAX_PLATFORMS", "RT_CHIP_GUARD_PLATFORMS",
            "TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_PROCESS_BOUNDS",
            "TPU_PROCESS_BOUNDS", "JAX_COMPILATION_CACHE_DIR")
    return {"pid": os.getpid(), "jax_imported": "jax" in _sys.modules,
            "backend": backend_initialized(),
            **{k: os.environ.get(k) for k in keys}}


def test_workers_on_a_tpu_node_with_and_without_a_lease():
    """On a node that advertises chips: a task with no chip lease runs
    guarded (and its jax is the CPU's), a task that leases chips sees
    exactly them in a worker that never ran anything, and that worker is
    retired when the lease ends."""
    ray_tpu.init(mode="cluster", num_cpus=3, num_tpus=4)
    try:
        plain = ray_tpu.remote(_worker_view)
        leased = ray_tpu.remote(num_tpus=1, num_cpus=0)(_worker_view)

        @ray_tpu.remote
        def uses_jax():
            import jax

            return os.getpid(), jax.devices()[0].platform

        v = ray_tpu.get(plain.remote(), timeout=120)
        assert v["JAX_PLATFORMS"] == "cpu"
        assert v["RT_CHIP_GUARD_PLATFORMS"] == "cpu"   # the suite's own
        assert v["TPU_VISIBLE_CHIPS"] is None
        assert v["JAX_COMPILATION_CACHE_DIR"]
        pid, platform = ray_tpu.get(uses_jax.remote(), timeout=120)
        assert platform == "cpu"

        seen = []
        for _ in range(3):
            a = ray_tpu.get(leased.remote(), timeout=120)
            assert a["RT_CHIP_GUARD_PLATFORMS"] is None    # lifted
            assert a["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
            assert a["TPU_PROCESS_BOUNDS"] == "1,1,1"
            assert a["TPU_VISIBLE_CHIPS"] in {"0", "1", "2", "3"}
            assert not a["backend"]
            seen.append(a["pid"])
        # A worker that used jax is never handed chips.  (The owner may
        # run all three tasks on one lease, so on one worker.)
        assert pid not in seen
        # The lease ends when the owner's pool lets it go; its worker is
        # retired with it, and the chips come back once it is gone.
        import time

        deadline = time.time() + 30
        while time.time() < deadline and \
                ray_tpu.available_resources().get("TPU") != 4.0:
            time.sleep(0.2)
        assert ray_tpu.available_resources().get("TPU") == 4.0
        # use_tpu asks for what one node has, and a demand no node can
        # meet fails at once instead of waiting out a placement timeout.
        from ray_tpu.train import ScalingConfig
        from ray_tpu.train.worker_group import WorkerGroup

        assert ScalingConfig(use_tpu=True).worker_resources() == {
            "CPU": 1.0, "TPU": 4.0}
        t0 = time.time()
        with pytest.raises(RuntimeError, match="largest node has 4"):
            WorkerGroup(1, resources_per_worker={"CPU": 1, "TPU": 8})
        assert time.time() - t0 < 5
        all4 = ray_tpu.remote(num_tpus=4, num_cpus=0)(_worker_view)
        b = ray_tpu.get(all4.remote(), timeout=120)
        assert sorted(b["TPU_VISIBLE_CHIPS"].split(",")) == \
            ["0", "1", "2", "3"]
        assert b["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "2,2,1"
        assert b["pid"] not in seen and b["pid"] != pid
    finally:
        ray_tpu.shutdown()


def test_use_tpu_without_a_tpu_node_says_so(local_runtime):
    from ray_tpu.train import ScalingConfig

    with pytest.raises(RuntimeError, match="no node of this cluster"):
        ScalingConfig(use_tpu=True).worker_resources()
    assert ScalingConfig().worker_resources() == {"CPU": 1.0}
