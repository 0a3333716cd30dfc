"""The whole of benchmark/run.py on the CPU at a tiny preset, one cell for
each traffic kind and the four-chip cell on four virtual CPU devices: the
real harness, the real readers, the real reference check; the last line
checked against the contract's keys.  The tiny cells are ADDED to a copy
of the benchmark as new files and entries (rehearsal.py), which is also
how a later PR adds a cell.  A rehearsal's line says platform "cpu": it is
no measurement."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import rehearsal


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearsal.build(str(tmp_path_factory.mktemp("bench")))


def _last_line(out):
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    assert "info" in json.loads(lines[-2])
    return json.loads(lines[-1])


def _check_contract(line, cell_metrics, count, trace):
    assert {"correct", "attempted", "failed", "metrics", "device"} <= \
        set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) <= set(cell_metrics)
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
    dev = line["device"]
    assert (dev["platform"], dev["count"]) == ("cpu", count)
    assert dev["memory_peak_bytes"] > 0
    if trace:
        assert 0 < dev["busy_s"] <= dev["window_s"]
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        for rows in line["breakdown"].values():
            assert 0 < len(rows) <= 10
            assert all(isinstance(n, str) and s >= 0 for n, s in rows)
    else:
        assert "breakdown" not in line and "busy_s" not in dev


CASES = {
    "tiny-train": (1, {"train_tokens_per_s", "setup_s"},
                   {"runtime.gang_start_s", "input.wait_share",
                    "trainer.host_ms", "step.device_ms", "step.mfu_pct"}),
    "tiny-sat": (1, {"serve_tokens_per_s", "setup_s"},
                 {"runtime.gang_start_s", "engine.step_ms.sat",
                  "engine.occupancy"}),
    "tiny-steady": (1, {"ttft_p95_ms", "itl_p95_ms", "setup_s"},
                    {"runtime.gang_start_s"}),
    "tiny-train-2x2": (4, {"train_tokens_per_s", "setup_s"},
                       {"runtime.gang_start_s", "input.wait_share",
                        "trainer.host_ms", "step.device_ms", "step.mfu_pct",
                        "collective.exposed_ms"}),
}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", sorted(CASES))
def test_cell_rehearsed_on_cpu(root, cell, trace):
    chips, e2e, layer = CASES[cell]
    out = rehearsal.run_cell(root, cell, chips=chips, devices=chips,
                             trace=trace)
    line = _last_line(out)
    want = layer if trace else e2e
    _check_contract(line, want, chips, trace)
    # every metric that needs no device plane is there (kernel and
    # program times need a TPU's trace: their readers return nothing here)
    assert set(line["metrics"]) == want


def test_a_later_pr_adds_a_cell_with_files_and_entries_only(root):
    """A traffic mix, a cell and a per-layer metric, each a NEW file, and
    three NEW entries in BENCHMARK.json; no file that was there changes."""
    before = {}
    for d, _, files in os.walk(os.path.join(root, "benchmark")):
        for f in files:
            p = os.path.join(d, f)
            if "__pycache__" not in p:
                before[p] = open(p, "rb").read()
    traffic = dict(rehearsal.TINY_CLOSED, clients=2)
    rehearsal._write(os.path.join(
        root, "benchmark/traffic/tiny-closed-2.json"), traffic)
    rehearsal._write(os.path.join(root, "benchmark/cells/tiny-sat-2.json"),
                     {"engine": {"page_size": 4, "num_pages": 64,
                                 "max_batch": 2}})
    with open(os.path.join(
            root, "benchmark/layer_metrics/engine.prefill_share.py"),
            "w") as f:
        f.write('"""engine: prompt tokens prefilled per token generated."""\n'
                "from benchmark.harness import readers\n\n\n"
                "def read(ctx):\n"
                '    return 100.0 * readers.stats_delta(ctx, "prefill_tokens")'
                ' / readers.stats_delta(ctx, "tokens_generated")\n')
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["workloads"].append({
        "name": "tiny-sat-2", "config": "gpt2-tiny",
        "traffic": "tiny-closed-2", "chips": 1, "why": "added by a test"})
    for m in manifest["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append("tiny-sat-2")
    manifest["per_layer"].append({
        "name": "engine.prefill_share", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "engine (llm/engine.py, kv_cache.py)",
        "moves": "serve_tokens_per_s", "workloads": ["tiny-sat-2"]})
    rehearsal._write(os.path.join(root, "BENCHMARK.json"), manifest)

    line = _last_line(rehearsal.run_cell(root, "tiny-sat-2", trace=1))
    assert line["correct"] is True
    assert set(line["metrics"]) == {"runtime.gang_start_s",
                                    "engine.prefill_share"}
    assert line["metrics"]["engine.prefill_share"]["value"] > 0
    for p, content in before.items():
        assert open(p, "rb").read() == content, p


def test_fewer_chips_than_the_cell_needs_prints_no_result(root):
    """As in the driver's sandbox: no device nodes.  Exit code not 0,
    nothing on stdout, and the message says what was found."""
    if os.path.isdir("/dev/vfio") or os.path.exists("/dev/accel0"):
        pytest.skip("this host shows TPU device nodes")
    out = rehearsal.run_cell(root, "tiny-train", chips=0)
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "found no accelerator" in out.stderr
    out = rehearsal.run_cell(root, "tiny-train-2x2", chips=1)
    assert out.returncode != 0 and out.stdout.strip() == ""


def test_a_worker_that_finds_another_platform_fails_the_run(root):
    """Pretended chips with the CPU behind them, but the run told to expect
    a TPU: the leased worker says what it found, and no result is
    printed."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=rehearsal.REPO + os.pathsep + rehearsal.HERE,
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    code = (
        "import sys, rehearse_run\n"
        f"run = rehearse_run.patch({root!r}, 1, platform='tpu')\n"
        "sys.exit(run.main(['--workload', 'tiny-train', '--seconds', "
        "'1']))\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "the leased train worker found" in out.stderr
    assert out.stdout.strip() == ""


def test_alone_in_a_directory_it_fails(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    paths: another exit code than 0 and no result."""
    shutil.copy(os.path.join(rehearsal.REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(rehearsal.BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "train-gpt2-124m", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0 and out.stdout.strip() == ""
