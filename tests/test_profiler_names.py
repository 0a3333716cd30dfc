"""One clock (PR 24): host phases as profiler annotations and stats()
counters, named kernels and scopes.  Plain asserts on names and counts:
``spans.annotate`` and who enters it, the engine's ``phase_s``, what a CPU
``jax.profiler`` capture of a tiny engine and of ``session.report`` holds,
the names in the lowered programs, that the scopes change no bit, and the
operator's capture options."""

import asyncio
import contextlib
import dataclasses
import glob
import re
import subprocess
import sys
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import ray_tpu
from ray_tpu.llm import engine as engine_mod
from ray_tpu.llm.engine import (PHASE_LEAVES, EngineConfig,
                                GenerationEngine, jit_forward)
from ray_tpu.llm.kv_cache import init_cache
from ray_tpu.models.gpt2 import GPT2, GPT2Config, gpt2_init, gpt2_loss_fn
from ray_tpu.train import session as session_mod
from ray_tpu.train.session import TrainSession, iter_device_batches
from ray_tpu.train.train_step import (TrainState, make_optimizer,
                                      make_sharded_train_step,
                                      make_train_step)
from ray_tpu.util import chips, spans, tracing

CFG = dataclasses.replace(GPT2Config.tiny(), remat=False,
                          dtype=jnp.float32)
TRAIN_CFG = dataclasses.replace(GPT2Config.tiny(), attn_impl="flash")


# ------------------------------------------------------------ the bridge

def test_annotate_without_jax_imports_none_and_is_one_shared_noop():
    code = (
        "import sys\n"
        "import ray_tpu.util.spans as s\n"
        "a = s.annotate('x', step=1)\n"
        "with a:\n"
        "    pass\n"
        "with s.span('y'):\n"
        "    pass\n"
        "assert a is s.annotate('z'), 'not one shared no-op'\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n"
        "assert [e['name'] for e in s.snapshot()] == ['y']\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr


def test_annotate_with_jax_loaded_is_a_trace_annotation():
    assert isinstance(spans.annotate("x", bucket=16),
                      jax.profiler.TraceAnnotation)


def test_annotate_never_raises_on_a_half_imported_jax(monkeypatch):
    monkeypatch.setitem(sys.modules, "jax", types.ModuleType("jax"))
    with spans.annotate("x", a=1):      # no jax.profiler yet: the no-op
        pass
    assert spans.annotate("x") is spans.annotate("y")


@pytest.mark.parametrize("opener", ["span", "start_span"])
def test_scoped_spans_enter_annotate_and_still_record(monkeypatch, opener):
    entered = []

    @contextlib.contextmanager
    def fake(name, **tags):
        entered.append(name)
        yield

    monkeypatch.setattr(spans, "annotate", fake)
    ring = spans.reset()
    with (spans.span("load") if opener == "span"
          else tracing.start_span("load")):
        assert entered == ["load"]
    assert [e["name"] for e in ring.snapshot()] == ["load"]


# ------------------------------------------------------------ the engine

def _engine(**kw):
    return GenerationEngine(
        model_cfg=CFG, params=gpt2_init(CFG, jax.random.PRNGKey(3)),
        engine_cfg=EngineConfig(page_size=4, num_pages=64, max_batch=4,
                                prefill_token_budget=64,
                                max_tokens_default=4, **kw))


# ------------------------------------------------- the phase accumulator

class _Clocks:
    """A wall clock and a CPU clock that move only when told to."""

    def __init__(self):
        self.wall = self.cpu = 0.0

    def spend(self, wall, cpu):
        self.wall += wall
        self.cpu += cpu

    def phases(self, leaves, other="x.other", **kw):
        return spans.Phases(leaves, other, clock=lambda: self.wall,
                            cpu_clock=lambda: self.cpu, **kw)


def test_phases_leaves_and_other_sum_to_the_whole_on_both_clocks():
    t = _Clocks()
    ph = t.phases(("x.a", "x.b"))
    for _ in range(3):
        with ph.whole():
            t.spend(1.0, 0.5)                   # the loop's own: other
            with ph.leaf("x.a", step=1):
                t.spend(4.0, 0.25)              # mostly waited
            with ph.leaf("x.b"):
                t.spend(2.0, 2.0)               # all computed
            ph.add("x.b", 0.5, 0.5)
            t.spend(0.5, 0.5)
        t.spend(100.0, 100.0)                   # between wholes: nobody's
    with ph.lock:
        s = ph.totals()
    assert ph.count == 3 and ph.longest_s == 7.5
    assert s["phase_s"] == {"x.a": 12.0, "x.b": 7.5}
    assert s["phase_cpu_s"] == {"x.a": 0.75, "x.b": 7.5}
    assert (s["step_s"], s["step_cpu_s"]) == (22.5, 9.75)
    assert s["x.other"] == 22.5 - 19.5
    assert s["step_cpu_s"] - sum(s["phase_cpu_s"].values()) == 1.5
    # the CPU clock was read in every whole: the sample is all of them
    assert s["cpu_sample"] == {"steps": 3, "phase_s": s["phase_s"],
                               "step_s": s["step_s"]}


def test_phases_read_the_cpu_clock_on_one_whole_in_cpu_every():
    """A CPU-clock read is a system call (6 us on the chip's host): the
    engine reads it on every 16th step.  The CPU sums are over those
    wholes, ``cpu_sample`` is the wall clock over the SAME wholes, and
    leaves + other = whole holds on either."""
    t = _Clocks()
    reads = []
    ph = spans.Phases(("x.a",), "x.other", cpu_every=3,
                      clock=lambda: t.wall,
                      cpu_clock=lambda: reads.append(1) or t.cpu)
    for k in range(7):              # wholes 0, 3 and 6 are sampled
        with ph.whole():
            t.spend(1.0, 1.0)
            with ph.leaf("x.a"):
                t.spend(2.0 + k, 0.5)
    with ph.lock:
        s = ph.totals()
    assert (ph.count, ph.cpu_count) == (7, 3)
    assert len(reads) == 3 * 4      # a whole's two and its leaf's two
    assert s["phase_s"] == {"x.a": 14.0 + 21.0} and s["step_s"] == 42.0
    assert s["cpu_sample"] == {"steps": 3, "phase_s": {"x.a": 6.0 + 9.0},
                               "step_s": 18.0}
    assert s["phase_cpu_s"] == {"x.a": 1.5} and s["step_cpu_s"] == 4.5


def test_phases_hand_over_once_a_whole_and_drop_a_void_one():
    t = _Clocks()
    ph = t.phases(("x.a",))
    ph.close()                      # no whole was open: only a start
    with ph.leaf("x.a"):
        t.spend(1.0, 1.0)
    with ph.lock:                   # mid-whole: nothing handed over yet
        mid = ph.totals()
    assert ph.count == 0 and mid["step_s"] == 0.0 == mid["phase_s"]["x.a"]
    t.spend(2.0, 0.0)
    ph.close()
    ph.void()                       # the next one holds a compile
    with ph.leaf("x.a"):
        t.spend(50.0, 50.0)
    ph.close()
    with ph.leaf("x.a"):
        t.spend(1.0, 0.0)
    ph.close()
    with ph.apart("x.elsewhere"):   # another thread's: its own sum
        t.spend(7.0, 0.0)
    with ph.lock:
        s = ph.totals()
    assert ph.count == 2 and ph.longest_s == 3.0
    assert s["phase_s"] == {"x.a": 2.0} and s["step_s"] == 4.0
    assert s["x.other"] == 2.0
    assert ph.apart_s == {"x.elsewhere": [7.0, 1]}


@pytest.fixture(scope="module")
def scripted():
    """No thread: two prompts of one bucket and one of another, stepped
    until all are done."""
    eng = _engine()
    for prompt in ([5, 6, 7], [8, 9, 10, 11], list(range(1, 12))):
        eng.submit(prompt, max_tokens=3)
    steps = 0
    while eng.step()["running"] or steps == 0:
        steps += 1
    return eng, steps + 1


def test_phase_counters_partition_the_step(scripted):
    eng, steps = scripted
    s = eng.stats()
    assert s["steps"] == steps
    assert tuple(s["phase_s"]) == PHASE_LEAVES == (
        "llm.cancel", "llm.admit", "llm.prefill.pack", "llm.prefill.run",
        "llm.prefill.fetch", "llm.prefill.sample", "llm.decode.pages",
        "llm.decode.pack", "llm.decode.run", "llm.decode.fetch",
        "llm.decode.sample", "llm.publish")
    assert all(v >= 0 for v in s["phase_s"].values())
    assert s["llm.other"] >= 0
    assert sum(s["phase_s"].values()) + s["llm.other"] == \
        pytest.approx(s["step_s"], rel=1e-12)
    assert s["phase_s"]["llm.decode.run"] > 0
    assert s["phase_s"]["llm.prefill.fetch"] > 0


def test_phase_cpu_counters_have_the_leaves_and_fit_inside_the_wall(
        scripted):
    """The engine thread's CPU clock beside the wall clock: the same
    leaves, no leaf computing for longer than it took, and the rest of
    the step's CPU time (llm.other's) not negative.  ``last_batch`` is
    the gauge's private field, no key of stats()."""
    eng, _ = scripted
    s = eng.stats()
    sample = s["cpu_sample"]        # the steps whose CPU time was read
    assert tuple(s["phase_cpu_s"]) == tuple(sample["phase_s"]) == \
        PHASE_LEAVES
    assert 1 <= sample["steps"] == -(-s["steps"] // 16)
    slack = 2e-3 * sample["steps"]  # the two clocks' own granularity
    for name, cpu in s["phase_cpu_s"].items():
        assert 0 <= cpu <= sample["phase_s"][name] + slack, name
        assert sample["phase_s"][name] <= s["phase_s"][name]
    assert 0 < s["step_cpu_s"] <= sample["step_s"] + slack
    assert sample["step_s"] <= s["step_s"]
    assert s["step_cpu_s"] - sum(s["phase_cpu_s"].values()) >= -slack
    assert "last_batch" not in s and not hasattr(engine_mod, "_Phase")


def test_prefills_and_compiles_count_what_the_script_did(scripted):
    eng, _ = scripted
    s = eng.stats()
    assert s["prefills"] == 3
    # buckets 8 and 16, the ONE placement of their served row, the
    # decode program, the one sampler and the feed of its ids to the
    # next step
    assert s["compiles"] == 6 == len(s["programs"])
    assert set(s["programs"]) == {"llm_prefill[8]", "llm_prefill[16]",
                                  "llm_last", "llm_decode", "llm_sample",
                                  "llm_feed"}


def test_stats_asks_the_device_nothing_and_peak_is_the_programs(
        scripted, monkeypatch):
    eng, _ = scripted

    def boom(*a, **kw):
        raise AssertionError("stats() queried the device")

    monkeypatch.setattr(chips, "peak_device_memory_bytes", boom)
    for d in jax.local_devices():
        monkeypatch.setattr(type(d), "memory_stats", boom, raising=False)
    s = eng.stats()
    totals = [engine_mod._program_bytes(exe)
              for _, exe in eng._exe_cache.values()]
    assert len(totals) == 6 and min(totals) > 0
    assert s["peak_hbm_bytes"] == max(totals)


# ----------------------------------------------------- what a capture holds

def _capture(tmp_path, fn):
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    events = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("llm.", "train.", "flash.")):
                    events.setdefault(e.name, []).append(dict(e.stats))
    return events


def test_cpu_capture_of_a_tiny_engine_holds_the_phases(tmp_path):
    eng = _engine()
    eng.submit(list(range(1, 12)), max_tokens=3, request_id="abc")
    eng.step()                  # bucket 16 and decode compile here

    def run():
        eng.submit([5, 6, 7], max_tokens=3)
        for _ in range(4):
            eng.step()

    events = _capture(tmp_path, run)
    assert set(PHASE_LEAVES) | {"llm.step", "llm.decode",
                                "llm.prefill"} <= set(events)
    assert len(events["llm.step"]) == 4
    assert {"step", "running", "waiting"} <= set(events["llm.step"][0])
    # one fetch after every launch, of the program before it, and the
    # last ids read in the step that launched them (the drain)
    assert len(events["llm.decode.fetch"]) == len(events["llm.decode"]) + 1
    prefill, = events["llm.prefill"]
    assert prefill["bucket"] == 8 and prefill["prompt_tokens"] == 3
    assert events["llm.decode"][0]["batch"] == 2
    # bucket 8 is new to this engine: its compile is inside the capture
    # (the placement of its row is bucket 16's program too)
    assert events["llm.compile"] == [{"program": "llm_prefill[8]"}]


def test_cpu_capture_fetches_name_the_flight_they_deliver(tmp_path):
    """``llm.decode.fetch`` / ``llm.prefill.fetch`` carry ``program`` and
    ``run``: the program whose ids the leaf read, which is the one
    launched BEFORE the annotation's own, and how many of it were
    delivered before; so by name the ordinals count up from the ledger's
    count at the capture's start."""
    eng = _engine()
    first = eng.submit(list(range(1, 12)), max_tokens=6)
    eng.step()
    eng.step()                  # a decode step of ``first`` is in the air
    before = eng.stats()["runs"]
    assert [f.name for f in eng._flights] == ["llm_decode"]

    def run():
        eng.submit([5, 6, 7], max_tokens=3)
        while not first.finished:
            eng.step()

    events = _capture(tmp_path, run)
    after = eng.stats()["runs"]
    # the fetch under the prefill's annotation read the decode step
    # launched before it; the prefill's own ids were read under the
    # next decode step's
    assert [e["program"] for e in events["llm.prefill.fetch"]] == \
        ["llm_decode"]
    fetched = events["llm.prefill.fetch"] + events["llm.decode.fetch"]
    assert {e["program"] for e in fetched} == {"llm_decode",
                                               "llm_prefill[8]"}
    for name in ("llm_decode", "llm_prefill[8]"):
        start = before.get(name, {"runs": 0})["runs"]
        assert sorted(e["run"] for e in fetched if e["program"] == name) \
            == list(range(start, after[name]["runs"]))
    assert after["llm_prefill[8]"]["runs"] == 1
    assert "llm_prefill[16]" not in {e["program"] for e in fetched}


def test_ids_are_fetched_after_the_next_launch_inside_its_annotation(
        monkeypatch):
    """The pipeline by the order of events on the host: every name is
    still entered, the leaves still sum to the step, and the fetch of
    program N lies after the launch of program N+1, inside the
    ``llm.decode`` / ``llm.prefill`` annotation that launched N+1 — where
    a capture of a device-bound engine finds run N+1's start."""
    log = []

    class Recorded:
        def __init__(self, name, **tags):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))

        def __exit__(self, *exc):
            log.append(("exit", self.name))
            return False

    monkeypatch.setattr(engine_mod, "annotate", Recorded)
    monkeypatch.setattr(spans, "annotate", Recorded)    # the leaves'
    eng = _engine()
    real_call, real_deliver = eng._call, eng._deliver
    launched = []                       # forwards, in launch order

    def call(fn, name, *args, **kwargs):
        if name.startswith(("llm_decode", "llm_prefill")):
            launched.append(name)
            log.append(("launch", len(launched) - 1))
        return real_call(fn, name, *args, **kwargs)

    fetched = []

    def deliver(leaves):
        # flights and forwards are launched one for one, in order
        log.append(("fetch", len(launched) - len(eng._flights)))
        fetched.append(len(eng._flights))
        return real_deliver(leaves)

    monkeypatch.setattr(eng, "_call", call)
    monkeypatch.setattr(eng, "_deliver", deliver)
    eng.submit(list(range(1, 12)), max_tokens=6)
    eng.step()
    eng.submit([5, 6, 7], max_tokens=4)
    while eng.step()["running"]:
        pass

    names = {name for what, name in log if what == "enter"}
    assert set(PHASE_LEAVES) | {"llm.step", "llm.decode",
                                "llm.prefill"} <= names
    s = eng.stats()
    assert sum(s["phase_s"].values()) + s["llm.other"] == \
        pytest.approx(s["step_s"], rel=1e-12)

    opened, launch_at, checked = [], {}, 0      # opened: (name, serial)
    for serial, (what, x) in enumerate(log):
        if what == "enter":
            opened.append((x, serial))
        elif what == "exit":
            assert opened.pop()[0] == x
        elif what == "launch":
            parent, leaf = opened[-2:]
            assert parent[0] == ("llm.decode" if launched[x] == "llm_decode"
                                 else "llm.prefill")
            assert leaf[0] == parent[0] + ".run"
            launch_at[x] = parent
        else:                           # the fetch of forward number x
            if x + 1 not in launch_at:  # the drain: nothing was launched
                assert x == len(launched) - 1
                assert [name for name, _ in opened] == ["llm.step"]
                continue
            # N+1 is launched, and that very annotation is still open
            assert opened[-1] == launch_at[x + 1]
            checked += 1
    assert len(launched) == 2 + 5 and checked == len(launched) - 1
    # two flights while the older is delivered, one in a drain
    assert fetched == [2] * checked + [1]
    pipeline = s["pipeline"]
    assert pipeline["launched_ahead"] == checked
    assert pipeline["drains"] == {"evict": 0, "error": 0, "empty": 1,
                                  "stop": 0}
    assert pipeline["rows_discarded"] == 0


class _FakeQueue:
    class push:                         # noqa: N801 — an actor method
        @staticmethod
        def remote(payload):
            return payload


def test_cpu_capture_of_session_report_holds_observe_and_push(
        tmp_path, monkeypatch):
    monkeypatch.setattr(ray_tpu, "get", lambda ref: ref)
    session = TrainSession(world_rank=0, world_size=1, local_rank=0,
                           local_world_size=1, node_rank=0,
                           experiment_name="t", result_queue=_FakeQueue())
    batches = iter_device_batches(
        [{"x": np.zeros((2, 2), np.float32)}] * 3)

    def run():
        for i, _ in enumerate(batches):
            session.report({"step": i, "tokens": 8})

    events = _capture(tmp_path, run)
    for name in ("train.report", "train.report.observe",
                 "train.report.push", "train.input.transfer"):
        assert len(events[name]) == 3, name


@pytest.fixture
def active_session(monkeypatch):
    monkeypatch.setattr(ray_tpu, "get", lambda ref: ref)
    session = session_mod.init_session(
        world_rank=0, world_size=1, local_rank=0, local_world_size=1,
        node_rank=0, experiment_name="t", result_queue=_FakeQueue())
    yield session
    session_mod.shutdown_session()


def test_scripted_session_partitions_its_period(active_session):
    """Input wait, dispatch, report: the four leaves and train.other sum
    to the period on both clocks; the prefetch thread's transfer is
    counted apart; the first report only starts the first period."""
    session = active_session
    batches = ray_tpu.train.iter_device_batches(
        [{"x": np.zeros((2, 2), np.float32)}] * 4, depth=1)
    for i, _ in enumerate(batches):
        with session_mod.step_account()[0].leaf(
                "train.step.dispatch", step=session_mod.step_account()[1]):
            pass
        session.report({"step": i, "tokens": 8})
    s = ray_tpu.train.stats()
    assert s == session.stats()
    assert set(s) == {"steps", "step_s", "step_cpu_s", "phase_s",
                      "phase_cpu_s", "train.other", "transfer_s",
                      "transfers", "longest_step_s", "cpu_sample"}
    assert s["cpu_sample"] == {"steps": 3, "phase_s": s["phase_s"],
                               "step_s": s["step_s"]}
    assert s["steps"] == 3
    assert tuple(s["phase_s"]) == tuple(s["phase_cpu_s"]) == \
        session_mod.TRAIN_LEAVES == (
            "train.input.wait", "train.step.dispatch",
            "train.report.observe", "train.report.push")
    assert sum(s["phase_s"].values()) + s["train.other"] == \
        pytest.approx(s["step_s"], rel=1e-12)
    assert s["train.other"] >= 0
    assert s["phase_s"]["train.report.push"] > 0
    assert 0 < s["step_cpu_s"] <= s["step_s"] + 2e-3 * s["steps"]
    assert s["step_s"] / s["steps"] <= s["longest_step_s"] <= s["step_s"]
    assert s["transfers"] == 4 and s["transfer_s"] > 0
    assert "train.input.transfer" not in s["phase_s"]


def test_cpu_capture_of_a_tiny_train_loop_pairs_dispatch_and_report(
        tmp_path, active_session):
    """What the gap readers pair a device run with: each step's
    ``train.step.dispatch`` and ``train.report`` carry the same ``step``,
    the session's report index, and a period that compiled the step is
    in no sum."""
    cfg = dataclasses.replace(GPT2Config.tiny(), attn_impl="dense")
    _, state, batch = _train_step_and_args(cfg)
    step = make_sharded_train_step(
        lambda p, b: gpt2_loss_fn(cfg, p, b), make_optimizer(),
        donate=False)

    def run():
        for i in range(4):      # the first call compiles
            _, metrics = step(state, batch)
            ray_tpu.train.report({"step": i,
                                  "loss": float(metrics["loss"])})

    events = _capture(tmp_path, run)
    assert len(events["train.step.compile"]) == 1
    assert [e["step"] for e in events["train.step.dispatch"]] == \
        [e["step"] for e in events["train.report"]] == [0, 1, 2, 3]
    s = active_session.stats()
    assert s["steps"] == 3 and s["phase_s"]["train.step.dispatch"] > 0
    assert s["longest_step_s"] < step.compile_seconds


def test_report_builds_each_metric_once(monkeypatch):
    from ray_tpu.util import metrics

    built = []
    for kind in ("Gauge", "Histogram"):
        real = getattr(metrics, kind)

        def counting(name, description, _real=real):
            built.append(name)
            return _real(name, description)

        monkeypatch.setattr(metrics, kind, counting)
    session = TrainSession(world_rank=0, world_size=1, local_rank=0,
                           local_world_size=1, node_rank=0,
                           experiment_name="t")
    for i in range(4):
        session.report({"step": i, "tokens": 8})
    assert sorted(built) == ["rt_train_step", "rt_train_step_time_seconds",
                             "rt_train_tokens_per_sec"]


# ------------------------------------------------- names in the programs

def _train_step_and_args(cfg=TRAIN_CFG):
    optimizer = make_optimizer()
    state = TrainState.create(gpt2_init(cfg, jax.random.PRNGKey(0)),
                              optimizer)
    batch = {"tokens": jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, cfg.max_seq + 1)), jnp.int32)}
    step = make_train_step(
        lambda p, b: gpt2_loss_fn(cfg, p, b, loss_chunk=32), optimizer)
    return step, state, batch


def _forward_and_args(cfg=CFG):
    params = gpt2_init(cfg, jax.random.PRNGKey(3))
    kv = init_cache(cfg.n_layer, 8, 4, cfg.n_head,
                    cfg.d_model // cfg.n_head, cfg.dtype)
    tokens = jnp.asarray([[5, 6, 7, 0]], jnp.int32)
    positions = jnp.asarray([[0, 1, 2, -1]], jnp.int32)
    table = jnp.asarray([[1, 2, 0, 0]], jnp.int32)
    return jit_forward(GPT2(cfg)), (params, tokens, kv["k_pages"],
                                    kv["v_pages"], table, positions)


def test_lowered_train_step_names_kernels_and_scopes():
    step, state, batch = _train_step_and_args()
    text = jax.jit(step).lower(state, batch).as_text(debug_info=True)
    for name in ("flash_fwd", "flash_dq", "flash_dkv", "loss_and_grad",
                 "optimizer", "grad_norm", "attn.qkv", "attn.core",
                 "attn.out", "mlp", "embed"):
        assert name in text, name
    # the chunked loss, forward and backward (a custom_vjp: both sides
    # are traced as jvp(loss))
    assert "loss_and_grad/jvp(loss)/" in text
    assert "transpose(loss_and_grad)/jvp(loss)/" in text


def test_lowered_train_step_keeps_the_logits_under_loss_or_lm_head():
    """step.loss_ms reads the scope ``loss``: every operation of the
    lowered step on an array of the logits' size (the vocabulary beside
    the batch and the chunk's rows; not wte's own [V, d]) lies under
    ``loss`` or ``lm_head``, forward and backward."""
    vocab = 320      # no other dimension of the tiny model
    step, state, batch = _train_step_and_args(
        dataclasses.replace(TRAIN_CFG, vocab_size=vocab))
    text = jax.jit(step).lower(state, batch).as_text(debug_info=True)
    locs = dict(re.findall(r"^(#loc\d+) = loc\((.*)\)$", text, re.M))
    logits_sized = re.compile(rf"tensor<(?:\d+x){{2,}}{vocab}x|"
                              rf"tensor<(?:\d+x)+{vocab}x(?:\d+x)+")
    # An operation inside a private function (the scan's body, one_hot,
    # take_along_axis) is named from its call site on: keep each
    # function's call sites beside the operations.
    func, call_sites, found = None, {}, []
    for line in text.splitlines():
        opened = re.match(r"\s*func\.func \w+ @(\w+)\(", line)
        if opened:
            func = opened.group(1)
            continue
        ref = re.search(r"loc\((#loc\d+)\)\s*$", line)
        if not ref:
            continue
        call = re.search(r"call @(\w+)\(", line)
        if call:
            call_sites.setdefault(call.group(1), []).append(
                (func, ref.group(1)))
        if logits_sized.search(line):
            found.append((func, ref.group(1), line))

    def names(func, ref):
        """Every name on the location's chain and on the chains of the
        calls that lead into ``func``."""
        out, refs = [], [ref]
        while refs:
            body = locs.get(refs.pop(), "")
            out += re.findall(r'"([^"]*)"', body)
            refs += re.findall(r"#loc\d+", body)
        for site in call_sites.get(func, []):
            out += names(*site)
        return out

    for func, ref, line in found:
        assert any(re.search(r"[/(](loss|lm_head)[)/]", name)
                   for name in names(func, ref)), line[:200]
    # the logits, the softmax's passes and the three matmuls at least
    assert len(found) >= 8


def test_lowered_engine_forward_names_the_cache_scopes():
    """A prefill stores its rows and attends among them (no ``kv.attend``:
    nothing is read from the pool); a decode step attends the pool."""
    fwd, args = _forward_and_args()
    text = fwd.lower(*args).as_text(debug_info=True)
    for name in ("kv.store", "attn.core", "embed", "lm_head"):
        assert name in text, name
    assert "kv.attend" not in text
    assert "jit_fwd" in text        # the reader of decode.device_ms.sat
    params, tokens, k_pages, v_pages, table, positions = args
    text = fwd.lower(params, tokens[:, 2:3], k_pages, v_pages, table,
                     positions[:, 2:3]).as_text(debug_info=True)
    for name in ("kv.store", "kv.attend", "attn.core", "jit_fwd"):
        assert name in text, name


def test_lowered_sampler_is_a_program_of_its_own_under_scope_sample():
    """A trace files the sampler's operations under ``sample``, in
    programs that are not ``jit_fwd``: decode.device_ms.sat reads the
    forward alone."""
    from ray_tpu.llm.sampling import jit_sampler, pack_rows

    sampler, last_rows = jit_sampler(4)
    logits = jnp.zeros((4, 1, 128), jnp.float32)
    text = sampler.lower(logits, *pack_rows([], 4)).as_text(debug_info=True)
    assert "jit_sample_tokens" in text and "jit_fwd" not in text
    assert "sample_tokens)/sample/" in text
    text = last_rows.lower(jnp.zeros((1, 1, 128), jnp.float32)
                           ).as_text(debug_info=True)
    assert "jit_last_rows" in text and "jit_fwd" not in text
    assert "last_rows)/sample/" in text


@pytest.mark.parametrize("what", ["train_step", "engine_forward"])
def test_scopes_change_no_bit(monkeypatch, what):
    def run():
        if what == "train_step":
            step, state, batch = _train_step_and_args()
            _, m = jax.jit(step)(state, batch)
            return [np.asarray(m["loss"]), np.asarray(m["grad_norm"])]
        fwd, args = _forward_and_args()
        logits, k, _ = fwd(*args)
        return [np.asarray(logits), np.asarray(k)]

    with_scopes = run()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = run()
    for a, b in zip(with_scopes, without):
        assert a.tobytes() == b.tobytes()


def test_timed_step_annotates_dispatch_and_builds_its_histogram_once(
        tmp_path, monkeypatch):
    """No histogram any more (nothing read it): outside a session the
    step's calls are the loop's periods, the dispatch is their leaf in
    ``train.stats()``, tagged with the call's own count, and the call
    that compiled is in no sum."""
    from ray_tpu.util import metrics

    built = []
    real = metrics.Histogram

    def counting(name, *a, **kw):
        built.append(name)
        return real(name, *a, **kw)

    monkeypatch.setattr(metrics, "Histogram", counting)
    monkeypatch.setattr(session_mod, "_loose", session_mod._new_phases())
    cfg = dataclasses.replace(GPT2Config.tiny(), attn_impl="dense")
    _, state, batch = _train_step_and_args(cfg)
    step = make_sharded_train_step(
        lambda p, b: gpt2_loss_fn(cfg, p, b), make_optimizer(),
        donate=False)

    def run():
        for _ in range(4):
            step(state, batch)

    events = _capture(tmp_path, run)
    assert len(events["train.step.compile"]) == 1
    assert [e["step"] for e in events["train.step.dispatch"]] == [0, 1, 2, 3]
    assert "rt_train_step_dispatch_seconds" not in built
    s = ray_tpu.train.stats()
    assert s["steps"] == 3
    assert 0 < s["phase_s"]["train.step.dispatch"] <= s["step_s"]
    assert sum(s["phase_s"].values()) + s["train.other"] == \
        pytest.approx(s["step_s"], rel=1e-12)
    assert s["longest_step_s"] < step.compile_seconds


def test_capture_of_a_step_compile_holds_the_flash_schedule(tmp_path):
    """The kernels' wrapper says, where the step is traced, how much of
    the score square they compute: a sequence of 512 in one block is
    walked in two strips, 3 of the 4 sub-block pairs (PR 34); and in
    which layout they read q, k, v: this model's two heads of 64 packed
    into one 128-lane block, one program for both (PR 36)."""
    cfg = dataclasses.replace(GPT2Config.tiny(), attn_impl="flash",
                              max_seq=512, n_layer=1, n_head=2)
    _, state, batch = _train_step_and_args(cfg)
    step = make_sharded_train_step(
        lambda p, b: gpt2_loss_fn(cfg, p, b, loss_chunk=128),
        make_optimizer(), donate=False)
    events = _capture(tmp_path, lambda: step(state, batch))
    assert len(events["train.step.compile"]) == 1
    assert events["flash.schedule"], sorted(events)
    for tags in events["flash.schedule"]:
        assert tags == {"t": 512, "block": 512, "sub": 256, "visited": 3,
                        "square": 4, "layout": "packed",
                        "heads_per_program": 2}


# --------------------------------------------------- the operator's capture

@pytest.mark.parametrize("request_fields,level", [
    ({}, 0), ({"python_tracer": False}, 0), ({"python_tracer": True}, 1)])
def test_worker_jax_profile_python_tracer_off_by_default(
        monkeypatch, tmp_path, request_fields, level):
    from ray_tpu.core.worker_main import Worker

    seen = {}
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda log_dir, profiler_options=None: seen.update(
            level=profiler_options.python_tracer_level, dir=log_dir))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    reply = asyncio.run(Worker.jax_profile(
        types.SimpleNamespace(),
        {"duration_s": 0.01, "log_dir": str(tmp_path), **request_fields}))
    assert reply == {"ok": True, "path": str(tmp_path)}
    assert seen == {"level": level, "dir": str(tmp_path)}


@pytest.mark.parametrize("flag", [False, True])
def test_state_and_cli_pass_python_tracer_through(monkeypatch, flag):
    from ray_tpu.scripts import cli
    from ray_tpu.util import state

    calls = []
    monkeypatch.setattr(state, "_agents", lambda node_id, address: [
        {"agent_addr": "a:1", "node_id": "n0"}])
    monkeypatch.setattr(
        state, "_agent_call",
        lambda addr, method, req: calls.append((method, req))
        or {"results": [{"pid": 1, "ok": True, "path": "/p"}]})
    monkeypatch.setattr(cli, "resolve_address", lambda address: "c:1")
    argv = ["profile", "--jax"] + (["--python-tracer"] if flag else [])
    args = cli._build_parser().parse_args(argv)
    assert args.fn(args) == 0
    (method, req), = calls
    assert method == "jax_profile_workers"
    assert req["python_tracer"] is flag
