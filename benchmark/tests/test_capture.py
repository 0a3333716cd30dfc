"""A traced serving run waits for its own capture (serve_runner
``_during`` / ``_await_timers``): the wait follows the capture's ONE budget,
and a run that ends without its trace says which of three things happened.
The wait alone against a fake handle, the budget and the margin scaled down
to a second; then the whole of run.py on the CPU: ``info``'s two keys, and
each fault of capture_faults.py ending the run with exit code 1 and its
reason as the last line of stderr."""
import concurrent.futures
import functools
import json
import os
import subprocess
import sys
import time
import types

import pytest

import rehearsal
from benchmark.harness import serve_runner
from benchmark.harness.cluster import BenchFailure

HERE = os.path.dirname(os.path.abspath(__file__))
BUDGET_S, MARGIN_S = 1.0, 0.3
OLD_WAIT_S = 120.0 / 300.0 * BUDGET_S      # the parent's join(timeout=120)


class FakeHandle:
    """``handle.method(name).remote(*args)`` on a thread; ``bench_trace``
    is the case's."""

    def __init__(self, bench_trace):
        self._pool = concurrent.futures.ThreadPoolExecutor(4)
        self._methods = {"bench_trace": bench_trace, "stats": dict}

    def method(self, name):
        return types.SimpleNamespace(remote=functools.partial(
            self._pool.submit, self._methods[name]))


@pytest.fixture
def futures_as_refs(monkeypatch):
    import ray_tpu

    def get(ref, timeout=None):
        try:
            return ref.result(timeout)
        except concurrent.futures.TimeoutError:
            raise ray_tpu.GetTimeoutError("get() exceeded its timeout")

    monkeypatch.setattr(ray_tpu, "get", get)


def _leaves_xplane(log_dir, seconds):
    time.sleep(OLD_WAIT_S + 0.2)           # slower than the old rule allowed
    d = os.path.join(log_dir, "plugins", "profile", "2026_10_05")
    os.makedirs(d)
    with open(os.path.join(d, "host.xplane.pb"), "wb") as f:
        f.write(b"x" * 1234)
    return log_dir


def _outlives_budget(log_dir, seconds):
    time.sleep(BUDGET_S + MARGIN_S + 1.0)
    return log_dir


def _raises(log_dir, seconds):
    raise RuntimeError("start_trace refused")


def _leaves_no_file(log_dir, seconds):
    d = os.path.join(log_dir, "plugins", "profile", "2026_10_05")
    os.makedirs(d)
    with open(os.path.join(d, "host.trace.json.gz"), "wb"):
        pass
    return log_dir


@pytest.mark.parametrize("bench_trace, says", [
    (_leaves_xplane, None),
    (_outlives_budget, ["still running", f"budget {BUDGET_S:g} s"]),
    (_raises, ["raised after", "RuntimeError('start_trace refused')"]),
    (_leaves_no_file, ["left no *.xplane.pb", "host.trace.json.gz"]),
], ids=["slower_than_old_wait", "outlives_budget", "raises", "no_file"])
def test_the_wait_follows_the_captures_budget(tmp_path, futures_as_refs,
                                              bench_trace, says):
    box, seconds = {}, 0.3
    during = serve_runner._during(str(tmp_path), box, FakeHandle(bench_trace),
                                  seconds, True, budget_s=BUDGET_S)
    t0 = time.perf_counter()
    during(t0)
    time.sleep(seconds)                    # the window; the capture is due
    if says is None:
        serve_runner._await_timers(box, margin_s=MARGIN_S)
        assert box["xplane"].endswith("host.xplane.pb")
        assert box["xplane_bytes"] == 1234
        assert OLD_WAIT_S < box["capture_s"] < BUDGET_S
    else:
        with pytest.raises(BenchFailure) as e:
            serve_runner._await_timers(box, margin_s=MARGIN_S)
        assert all(s in str(e.value) for s in says), str(e.value)
        assert "xplane" not in box and "\n" not in str(e.value)
        if bench_trace is _outlives_budget:
            waited = float(str(e.value).split("still running ")[1].split()[0])
            assert BUDGET_S <= waited <= BUDGET_S + MARGIN_S + 0.5
    assert time.perf_counter() - t0 < seconds + BUDGET_S + MARGIN_S + 1.0
    assert {"at_start", "at_end"} <= set(box)       # the snapshots' own wait


def test_an_untraced_run_waits_for_its_snapshots_alone(tmp_path,
                                                       futures_as_refs):
    box = {}
    serve_runner._during(str(tmp_path), box, FakeHandle(_raises), 0.1,
                         False)(time.perf_counter())
    serve_runner._await_timers(box, margin_s=MARGIN_S)
    assert {"at_start", "at_end"} <= set(box)
    assert not {"capture", "t_capture", "no_capture", "xplane"} & set(box)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearsal.build(str(tmp_path_factory.mktemp("bench")))


def test_info_carries_what_the_capture_cost_in_a_traced_run_only(root):
    details = {}
    for trace in (1, 0):
        out = rehearsal.run_cell(root, "tiny-sat", trace=trace)
        assert out.returncode == 0, out.stderr[-3000:]
        lines = out.stdout.strip().splitlines()
        details[trace] = json.loads(lines[-2])["info"]["detail"]
        assert set(json.loads(lines[-1])) <= {
            "correct", "attempted", "failed", "metrics", "device",
            "breakdown"}                    # nothing new in the result line
        assert ("capture_s" in out.stderr) == bool(trace)   # the log line
    traced, untraced = details[1], details[0]
    assert traced["capture_s"] > serve_runner.TRACE_SECONDS
    assert traced["xplane_bytes"] > 0
    assert set(traced) - set(untraced) >= {"capture_s", "xplane_bytes"}
    assert not {"capture_s", "xplane_bytes"} & set(untraced)


@pytest.mark.parametrize("fault, says", [
    ("slow", "the profiler capture was still running"),
    ("raises", "the profiler capture raised after"),
    ("empty", "left no *.xplane.pb under"),
])
def test_a_run_without_its_trace_says_why_last_on_stderr(root, fault, says):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=rehearsal.REPO + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "capture_faults.py"), fault,
         "--rehearse", root, "--workload", "tiny-sat", "--seed", "11",
         "--seconds", "3", "--trace", "1"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    last = out.stderr.strip().splitlines()[-1]
    assert out.returncode == 1, out.stderr[-3000:]
    assert last.startswith("benchmark: FAILED: BenchFailure(") and says in last
    assert {"slow": "budget 2 s", "raises": "capture_faults: start_trace "
            "refused", "empty": "host.trace.json.gz"}[fault] in last
    assert not any(line.startswith("{")     # no result line, no info line
                   for line in out.stdout.splitlines())
