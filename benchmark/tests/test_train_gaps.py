"""benchmark/harness/train_gaps.py (PR 35) on a trace worked out by hand:
four runs of the step program, so three whole gaps (the first one long: a
stall), two chips that differ in the hole inside a run, a stray report at
the window's head that pairing by position would trip over; on the same
trace without the ``step`` tags (the parent's kind); and the engine's
off-CPU time from two ``stats()`` readings."""
import pytest

from benchmark.harness import manifest, trace as T, train_gaps as G
from xplane_stats import encode

us = 1000.0     # the trace's times are nanoseconds
OP_A = "%fusion.1 = bf16[8,128]{1,0} fusion(bf16[8,128]{1,0} %p.1), kind=kOutput, calls=%fused_computation.1"
OP_B = "%fusion.2 = f32[128]{0} fusion(f32[128]{0} %p.2), kind=kLoop, calls=%fused_computation.2"
RUN_STARTS = (100, 1500, 2400, 3300)        # each run's first operation
NEW_TRAIN = ("trainer.gap_ms", "trainer.return_ms", "trainer.launch_ms",
             "input.wait_ms", "step.idle_inside_ms")


def _trace(tmp_path, tags: bool, host_late: float = 0.0):
    """Per run: an operation of 300 us, a hole (20 us on chip 0, 40 on
    chip 1), a second operation up to 700 us after the start.  After each
    run: 30 us until ``train.report`` (40 us); then the loop's own code, a
    20 us input wait before step 8 only, and ``train.step.dispatch`` (50
    us) starting 100 us (step 8) or 110 us before the next run's first
    operation.  Step 8's dispatch comes 510 us late: the stall.  The
    window is [0, 4100) us.  ``host_late``: the host's clock runs that
    many us behind the device's (the window's annotation apart)."""
    planes = []
    for chip, hole in ((0, 20), (1, 40)):
        ops, modules = [], []
        for s in RUN_STARTS:
            ops += [(OP_A, s * us, 300 * us),
                    (OP_B, (s + 300 + hole) * us, (400 - hole) * us)]
            modules.append(("jit_step(1)", (s - 2) * us, 704 * us))
        modules.append(("jit_other(2)", 5000 * us, 10 * us))    # outside
        planes.append((f"/device:TPU:{chip}",
                       {"XLA Modules": modules, "XLA Ops": ops}))

    def tag(step):
        return ({"step": step},) if tags else ()

    host = [(T.WINDOW_ANNOTATION, 0, 4100 * us)]
    if tags:    # the report of the step before the window, cut by its start
        host.append(("train.report", 10 * us, 20 * us, {"step": 6}))
    for k, s in enumerate(RUN_STARTS):
        lead = 60 if k == 0 else 100 if k == 1 else 110
        host.append(("train.step.dispatch", (s - lead) * us, 50 * us,
                     *tag(7 + k)))
        host.append(("train.report", (s + 730) * us, 40 * us, *tag(7 + k)))
        host.append(("train.report.push", (s + 735) * us, 30 * us))
    host.append(("train.input.wait", 880 * us, 20 * us))
    host.append(("train.input.transfer", 2000 * us, 300 * us))
    host = host[:1] + [(e[0], e[1] + host_late * us, *e[2:])
                       for e in host[1:]]
    planes.append(("/host:CPU", {
        "python/1": [e for e in host if e[0] != "train.input.transfer"],
        "prefetch/2": [e for e in host if e[0] == "train.input.transfer"]}))
    path = tmp_path / f"gaps_{int(tags)}_{int(host_late)}.xplane.pb"
    path.write_bytes(encode(planes))
    return {"trace_path": str(path),
            "trace": T.reduce(T.load(str(path))),
            "train": {"records": [{"traced": True}] * 4}}


def test_gaps_by_hand_paired_by_the_step_tag(tmp_path):
    ctx = _trace(tmp_path, tags=True)
    cap = G.capture(ctx)
    assert (cap["paired_by"], cap["runs"], cap["devices"]) == ("step", 4, 2)
    assert cap["program"] == "jit_step(1)"
    # pairing by the tag: each gap is named by the step whose run it
    # precedes, and the stray report of step 6 moved nothing
    assert [g["step"] for g in cap["gap_ms_by_step"]] == [8, 9, 10]
    want = [
        {"gap": 0.700, "return": 0.030, "report": 0.040,
         "input_wait": 0.020, "own": 0.510, "launch": 0.100},
        {"gap": 0.200, "return": 0.030, "report": 0.040,
         "input_wait": 0.0, "own": 0.020, "launch": 0.110},
        {"gap": 0.200, "return": 0.030, "report": 0.040,
         "input_wait": 0.0, "own": 0.020, "launch": 0.110}]
    for got, w in zip(cap["gap_ms_by_step"], want):
        assert {k: got[k] for k in w} == pytest.approx(w)
        # the five parts sum to the gap
        assert sum(got[p] for p in G.PARTS) == pytest.approx(got["gap"])
        assert got["dispatch"] == pytest.approx(0.050)
    assert cap["parts_less_gap_ms"] == pytest.approx(0.0, abs=1e-9)
    # the list shows the stall, the median does not
    assert max(g["gap"] for g in cap["gap_ms_by_step"]) == \
        pytest.approx(0.700)
    assert cap["gap_ms"] == pytest.approx(0.200)
    assert cap["gap_mean_ms"] == pytest.approx(1.1 / 3)
    assert cap["parts_ms"] == pytest.approx({
        "return": 0.030, "report": 0.040, "input_wait": 0.0,
        "own": 0.020, "launch": 0.110, "dispatch": 0.050})
    # the window's edges: 100 us before the first run (40 own, with the
    # stray report in it, 60 launch), 100 after the last (30 + 40 + 30)
    assert {k: cap["edge_ms"][k] for k in ("gap", "head", "tail") + G.PARTS
            } == pytest.approx({
                "gap": 0.200, "head": 0.100, "tail": 0.100,
                "return": 0.030, "report": 0.040, "input_wait": 0.0,
                "own": 0.070, "launch": 0.060})
    # inside: 20 us a run on one chip, 40 on the other
    assert cap["inside_ms_by_run"] == pytest.approx([0.030] * 4)
    assert cap["inside_ms"] == pytest.approx(0.030)
    # inside + between = the window's idle, by trace.reduce's count too
    idle = cap["idle_ms"]
    assert idle["between"] == pytest.approx(1.300)
    assert idle["inside"] == pytest.approx(0.120)
    assert idle["window"] == pytest.approx(1.420)
    assert idle["window_by_reduce"] == pytest.approx(1.420)
    assert idle["ratio"] == pytest.approx(1.0)
    assert (cap["input_wait_ms"], cap["input_waits"]) == \
        (pytest.approx(0.005), 1)
    assert (cap["input_transfer_ms"], cap["input_transfers"]) == \
        (pytest.approx(0.300), 1)
    for name, value in zip(NEW_TRAIN, (0.200, 0.030, 0.110, 0.005, 0.030)):
        assert manifest.load_reader(name)(ctx) == pytest.approx(value), name
    assert ctx["info"]["phases"]["train_gaps"] is cap


def test_a_host_clock_that_runs_late_still_pairs_each_run_with_its_step(
        tmp_path):
    """My chip run f7 (PR 35): every dispatch seemed to start AFTER the
    run it launched, "the last dispatch before the run" named the step
    before and gave the whole gap to the launch.  Paired with the
    nearest one the steps are right, the launch reads 0 (clipped), the
    return takes its share and the parts still sum to the same gaps."""
    cap = G.capture(_trace(tmp_path, tags=True, host_late=150))
    assert cap["paired_by"] == "step"
    assert [g["step"] for g in cap["gap_ms_by_step"]] == [8, 9, 10]
    assert [g["gap"] for g in cap["gap_ms_by_step"]] == \
        pytest.approx([0.700, 0.200, 0.200])
    assert {k: cap["gap_ms_by_step"][1][k] for k in G.PARTS} == \
        pytest.approx({"return": 0.180, "report": 0.020, "input_wait": 0.0,
                       "own": 0.0, "launch": 0.0})
    assert cap["parts_less_gap_ms"] == pytest.approx(0.0, abs=1e-9)
    assert cap["idle_ms"]["ratio"] == pytest.approx(1.0)


def test_the_parents_trace_is_listed_by_position_and_read_by_no_tag_reader(
        tmp_path):
    ctx = _trace(tmp_path, tags=False)
    cap = G.capture(ctx)
    assert cap["paired_by"] == "position"
    assert [g["step"] for g in cap["gap_ms_by_step"]] == [1, 2, 3]
    assert [g["gap"] for g in cap["gap_ms_by_step"]] == \
        pytest.approx([0.700, 0.200, 0.200])
    assert cap["gap_ms_by_step"][0]["return"] == pytest.approx(0.030)
    assert G.tagged(ctx) is None
    values = {name: manifest.load_reader(name)(ctx) for name in NEW_TRAIN}
    assert values == {"trainer.gap_ms": None, "trainer.return_ms": None,
                      "trainer.launch_ms": None,
                      "input.wait_ms": pytest.approx(0.005),
                      "step.idle_inside_ms": pytest.approx(0.030)}


def test_a_trace_without_a_step_program_gives_nothing(tmp_path):
    path = tmp_path / "empty.xplane.pb"
    path.write_bytes(encode([
        ("/device:TPU:0", {"XLA Modules": [], "XLA Ops": [
            (OP_A, 10 * us, 5 * us)]}),
        ("/host:CPU", {"python/1": [(T.WINDOW_ANNOTATION, 0, 100 * us)]})]))
    ctx = {"trace_path": str(path), "train": {"records": [{"traced": True}]}}
    assert G.capture(ctx) is None
    for name in NEW_TRAIN:
        assert manifest.load_reader(name)(ctx) is None, name
    assert manifest.load_reader("engine.offcpu_ms.sat")(ctx) is None


def test_engine_offcpu_is_wall_less_cpu_outside_the_fetches():
    """1600 steps of which the engine read the CPU clock in 100, each of
    those 10 ms: fetch 4 ms of which 0.5 computed, run 3 ms of which 2.5
    computed, llm.other 3 ms of which 1 computed.  The other steps (12
    ms each) are in ``phase_s`` / ``step_s`` and must not count."""
    def stats(steps, scale):
        return {"steps": 16 * steps, "step_s": 19.2 * scale,
                "step_cpu_s": 0.4 * scale,
                "phase_s": {"llm.decode.fetch": 9.0 * scale,
                            "llm.decode.run": 5.0 * scale},
                "phase_cpu_s": {"llm.decode.fetch": 0.05 * scale,
                                "llm.decode.run": 0.25 * scale},
                "cpu_sample": {
                    "steps": steps, "step_s": 1.0 * scale,
                    "phase_s": {"llm.decode.fetch": 0.4 * scale,
                                "llm.decode.run": 0.3 * scale}}}

    ctx = {"serve": {"before": stats(50, 1.0), "at_end": stats(150, 2.0)}}
    assert manifest.load_reader("engine.offcpu_ms.sat")(ctx) == \
        pytest.approx(6.0 - 3.5)
    note = ctx["info"]["phases"]["engine_offcpu_ms"]
    assert note["offcpu_ms"] == pytest.approx({
        "llm.decode.fetch": 3.5, "llm.decode.run": 0.5, "llm.other": 2.0})
    assert (note["step_ms"], note["step_cpu_ms"]) == pytest.approx((10, 4))
    assert (note["steps_sampled"], note["steps"]) == (100, 1600)
    # the parent's stats() have no CPU clock: nothing to read
    for s in (ctx["serve"]["before"], ctx["serve"]["at_end"]):
        del s["phase_cpu_s"], s["cpu_sample"]
    assert manifest.load_reader("engine.offcpu_ms.sat")(ctx) is None
