"""CPU rehearsal of chip_smoke.py: the script's own phases, arguments and
order at a tiny model size, with the device check steered from here
(``platform="cpu"``, interpret-mode kernel, fake chips on the node), so a
later PR cannot break the script without tier-1 noticing.  On the chip the
same code runs at GPT-2 124M through ``python chip_smoke.py``."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod
    spec.loader.exec_module(mod)
    yield mod
    sys.modules.pop("chip_smoke", None)


def _tiny(spec: dict, out_dir) -> dict:
    spec.update(
        model=dict(n_layer=2, n_head=4, d_model=64, d_ff=128,
                   vocab_size=512, max_seq=128),
        loss_chunk=64, global_batch=4, steps=4, rows_per_block=4,
        token_subset=16, platform="cpu", kernel_marker=None,
        device_nodes=False, page_size=4, num_pages=64, max_batch=4,
        prompt_lens=[3, 9, 20], max_tokens=4, out_dir=str(out_dir))
    return spec


def _lines(capsys):
    out = capsys.readouterr().out
    return [json.loads(l) for l in out.splitlines() if l.startswith("{")]


def test_one_chip_phases_rehearsed_on_cpu(smoke, tmp_path, monkeypatch,
                                          capsys):
    # One CPU device per process, as one chip would be.
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=1")
    spec = _tiny(smoke.one_chip_spec(seed=3), tmp_path)
    rc = smoke.run(spec, num_tpus=1, num_cpus=4)
    lines = _lines(capsys)
    assert rc == 0, lines
    assert [(l.get("phase"), l.get("check")) for l in lines[:-1]] == [
        ("start", None), ("train", "chip_owner"), ("train", None),
        ("serve", "chip_owner"), ("serve", None), ("shutdown", None)]
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    start, owner, train, s_owner, serve, down = lines[:-1]
    assert start["node_tpus"] == 1.0
    assert start["compile_cache"]["dir"]
    assert owner["ok"] and owner["chipless_task"]["platform"] == "cpu"
    assert str(train["device"]["pid"]) in owner["lease_pids"]
    assert train["steps"] == 4 and train["losses"][-1] < train["losses"][0]
    assert train["aot_executable_ran"] and train["compile_s"] > 0
    assert str(serve["device"]["pid"]) in s_owner["lease_pids"]
    assert serve["device"]["pid"] != train["device"]["pid"]
    assert serve["step_errors"] == 0 and serve["requests"] == 5
    assert down["processes_left"] == []


def test_four_chip_phase_rehearsed_on_cpu(smoke, tmp_path, monkeypatch,
                                          capsys):
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=4")
    spec = _tiny(smoke.four_chip_spec(seed=3), tmp_path)
    assert spec["phases"] == ["train"] and spec["chips"] == 4
    rc = smoke.run(spec, num_tpus=4, num_cpus=4)
    lines = _lines(capsys)
    assert rc == 0, lines
    assert [l.get("phase") for l in lines[:-1]] == [
        "start", "train", "train", "shutdown"]
    assert lines[-1]["device"]["count"] == 4
    train = lines[2]
    assert train["mesh"] == {"fsdp": 2, "tensor": 2}
    assert len(train["unsharded_losses"]) == len(train["losses"]) == 4
    assert train["max_abs_loss_diff"] <= spec["loss_tolerance"]
    for kind in ("params", "opt_state"):
        assert len(train["bytes_held_per_device"][kind]) == 4
    axes = train["collective_bytes_by_axis"]
    assert any("fsdp" in a for a in axes) and \
        any("tensor" in a for a in axes), axes


def test_worker_that_finds_no_tpu_fails_the_run(smoke, tmp_path,
                                                monkeypatch, capsys):
    """Fake chips on the node, the CPU behind them, and the real spec's
    platform: the leased worker says what it found and the script fails;
    nothing runs on the CPU in the chip's place."""
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=1")
    spec = _tiny(smoke.one_chip_spec(), tmp_path)
    spec.update(platform="tpu", device_nodes=False)
    rc = smoke.run(spec, num_tpus=1, num_cpus=4)
    captured = capsys.readouterr()
    assert rc == 1
    assert "the leased train worker found" in captured.err
    lines = [json.loads(l) for l in captured.out.splitlines()
             if l.startswith("{")]
    assert not any(l.get("phase") == "serve" for l in lines)
    assert "ok" not in lines[-1] or lines[-1].get("phase")


def test_no_tpu_exits_nonzero_and_prints_no_result():
    """As the driver's sandbox runs it: no device nodes, JAX held to the
    CPU.  It says that it found no TPU and starts nothing."""
    if os.path.isdir("/dev/vfio") or os.path.exists("/dev/accel0"):
        pytest.skip("this host shows TPU device nodes")
    out = subprocess.run([sys.executable, SCRIPT], capture_output=True,
                         text=True, timeout=120,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert "found no TPU" in out.stderr
    assert out.stdout.strip() == ""


def test_script_alone_fails(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "chip_smoke.py"],
                         capture_output=True, text=True, timeout=120,
                         cwd=tmp_path, env=env)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_kernel_compile_failure_surfaces_and_nothing_falls_back(
        monkeypatch):
    """The step's one way to compile: with the kernel's compile made to
    fail, the first call raises the compiler's error — no retry through
    another path, no executable left behind."""
    import jax
    import jax.numpy as jnp

    import ray_tpu.ops
    from ray_tpu.models.gpt2 import GPT2Config, gpt2_init, gpt2_loss_fn
    from ray_tpu.train.train_step import (TrainState, make_optimizer,
                                          make_sharded_train_step)

    def refuse(*a, **k):
        raise RuntimeError("Mosaic failed to compile TPU kernel: injected")

    # (GPT-2's training block enters through flash_attention_qkv)
    monkeypatch.setattr(ray_tpu.ops, "flash_attention", refuse)
    monkeypatch.setattr(ray_tpu.ops, "flash_attention_qkv", refuse)
    cfg = GPT2Config(n_layer=1, n_head=2, d_model=32, d_ff=64,
                     vocab_size=64, max_seq=16, attn_impl="flash")
    optimizer = make_optimizer(total_steps=4)
    state = TrainState.create(gpt2_init(cfg, jax.random.PRNGKey(0)),
                              optimizer)
    step = make_sharded_train_step(
        lambda p, b: gpt2_loss_fn(cfg, p, b, loss_chunk=0), optimizer)
    batch = {"tokens": jnp.zeros((2, 17), jnp.int32)}
    with pytest.raises(RuntimeError, match="Mosaic failed to compile"):
        step(state, batch)
    assert step.compiled() is None
