"""The five readers of PR 51 (benchmark/harness/run_ledger.py): the four
that take the window's delta of ``stats()["runs"]`` on counters written
out by hand, and the one that pairs a capture's ``jit_fwd`` runs with the
tagged ``.fetch`` annotations in order, on a capture made by hand
(xplane_stats.py: xplane_writer.py with the annotations' tags)."""
import pytest

from benchmark.harness import manifest, run_ledger

CELLS = ["serve-gpt2-large-sat", "serve-olmoe-1b-7b-sat",
         "serve-granite-4.0-h-small-sat", "serve-lfm2-24b-a2b-sat",
         "serve-kimi-k2.5-4k", "serve-kimi-linear-48b-a3b-longout",
         "serve-xing4.0-29b-a4b-4k", "serve-olmo-hybrid-7b-4k"]
COUNTERS = ["decode.paced_ms.sat", "prefill.paced_share.sat",
            "prefill.paced_ms_per_ktok.sat", "engine.stall_share.sat"]


def _program(runs, rows, tokens, by_bin):
    """A ledger entry: ``by_bin`` is {bin: (count, seconds)}."""
    by_ms, s_by_ms = [0] * 16, [0.0] * 16
    for i, (n, s) in by_bin.items():
        by_ms[i], s_by_ms[i] = n, s
    return {"runs": runs, "rows": rows, "tokens": tokens,
            "paced_s": sum(s_by_ms), "by_ms": by_ms, "s_by_ms": s_by_ms}


def _stats(runs, unpaced=0.0, **other):
    return {"runs": runs, "runs_unpaced_s": unpaced, "runs_voided_s": 0.25,
            **other}


def _ctx(before, at_end, counted_s=30.0):
    return {"serve": {"before": before, "at_end": at_end,
                      "counted_s": counted_s}}


def _window():
    """30 s: 1,000 decode steps of 12 ms at 16 rows, 40 prefills of 1,024
    at 100 ms and 20 of 4,096 at 400 ms, on top of a warm-up's counts."""
    before = _stats({
        "llm_decode": _program(10, 20, 20, {4: (9, 0.1)}),
        "llm_prefill[1024]": _program(2, 2, 2048, {7: (1, 0.1)}),
        "llm_prefill[64]": _program(1, 1, 64, {})}, unpaced=5.0,
        prefills=6, tokens_generated=20, prefill_bucket_tokens=2112 + 8192,
        attention={"decode_runs": 10},
        pipeline={"rows_discarded": 3})
    at_end = _stats({
        "llm_decode": _program(1010, 16020, 16020,
                               {4: (1009, 12.1)}),
        "llm_prefill[1024]": _program(42, 42, 2048 + 40 * 1024,
                                      {7: (41, 4.1)}),
        "llm_prefill[4096]": _program(20, 20, 20 * 4096, {9: (20, 8.0)}),
        "llm_prefill[64]": _program(1, 1, 64, {})}, unpaced=5.03,
        prefills=67, tokens_generated=16075, prefill_bucket_tokens=2112
        + 8192 + 40 * 1024 + 21 * 4096,
        attention={"decode_runs": 1010},
        pipeline={"rows_discarded": 8})
    return _ctx(before, at_end)


def test_the_counter_readers_take_the_windows_delta():
    ctx = _window()
    read = {name: manifest.load_reader(name)(ctx) for name in COUNTERS}
    assert read["decode.paced_ms.sat"] == pytest.approx(12.0)
    # 4.0 s + 8.0 s of 30
    assert read["prefill.paced_share.sat"] == pytest.approx(40.0)
    # 12 s over 40 x 1,024 + 20 x 4,096 = 122,880 bucket tokens
    assert read["prefill.paced_ms_per_ktok.sat"] == \
        pytest.approx(12000 / 122.88)
    assert read["engine.stall_share.sat"] == 0.0
    notes = ctx["info"]["phases"]
    assert notes["prefill_paced_ms_per_ktok_by_bucket"] == pytest.approx(
        {"1024": 100 / 1.024, "4096": 400 / 4.096})
    ledger = notes["run_ledger"]
    assert list(ledger["by_program"]) == [
        "llm_decode", "llm_prefill[1024]", "llm_prefill[4096]"]
    assert ledger["by_program"]["llm_prefill[4096]"] == {
        "runs": 20, "rows": 20, "tokens": 81920, "paced_s": 8.0,
        "paced_ms_a_run": 400.0, "share_pct": pytest.approx(80 / 3),
        "by_ms": {"256-512": 20}}
    # 24 s paced + 0.03 s nobody's of 30: a fifth of this window is lost
    assert ledger["paced_plus_unpaced_over_counted_less_one"] == \
        pytest.approx(24.03 / 30 - 1)
    # the engine's other counters: one 4,096 prefill is still in the air
    assert ledger["decode_runs"] == [1000, 1000]
    assert ledger["prefills"] == [60, 61]
    assert ledger["rows"] == [16000 + 40 + 20, 16055 + 5]
    assert ledger["prefill_tokens"] == [122880, 122880 + 4096]
    assert notes["stall"]["slowest_run"] == {
        "program": "llm_prefill[4096]", "ms": "256-512"}


def test_one_run_of_three_seconds_among_two_thousand_is_the_stall_share():
    clean = {"llm_decode": _program(2000, 32000, 32000, {4: (2000, 20.0)}),
             "llm_prefill[512]": _program(30, 30, 15360, {6: (30, 1.2)})}
    stalled = {**clean, "llm_decode": _program(
        2001, 32016, 32016, {4: (2000, 20.0), 12: (1, 3.0)})}
    nothing = _stats({})
    read = manifest.load_reader("engine.stall_share.sat")
    assert read(_ctx(nothing, _stats(clean), 21.2)) == 0.0
    ctx = _ctx(nothing, _stats(stalled), 24.2)
    # the 3 s less one usual run of 10 ms
    assert read(ctx) == pytest.approx(100 * 2.99 / 24.2)
    assert ctx["info"]["phases"]["stall"] == {
        "stalled_s": pytest.approx(2.99),
        "stalled_s_by_program": {"llm_decode": pytest.approx(2.99)},
        "slowest_run": {"program": "llm_decode", "ms": "2048-4096"}}
    # twice the usual is the next bin: no stall
    near = {"llm_decode": _program(2001, 0, 0, {4: (2000, 20.0),
                                                 5: (1, 0.02)})}
    assert read(_ctx(nothing, _stats(near), 20.0)) == 0.0
    # a void run (its launch compiled) is in no bin and no mean
    ctx = _ctx(nothing, _stats({"llm_decode": _program(
        2001, 0, 0, {4: (2000, 24.0)})}))
    assert manifest.load_reader("decode.paced_ms.sat")(ctx) == \
        pytest.approx(12.0)


@pytest.mark.parametrize("ctx", [
    {}, {"serve": {}}, _ctx({}, {}),
    _ctx({"steps": 1, "phase_s": {}}, {"steps": 9, "phase_s": {}}),
    _ctx(_stats({}), {"steps": 9}),
    _ctx(_stats({"llm_decode": _program(5, 5, 5, {3: (5, 0.02)})}),
         _stats({"llm_decode": _program(5, 5, 5, {3: (5, 0.02)})}))],
    ids=["nothing", "no_window", "empty", "the_parent", "one_edge",
         "nothing_ran"])
@pytest.mark.parametrize("name", COUNTERS + ["prefill.device_ms_per_ktok.sat"])
def test_readers_return_nothing_without_the_ledger(name, ctx):
    assert manifest.load_reader(name)(dict(ctx)) is None


def test_prefill_readers_return_nothing_in_a_window_of_decode_steps():
    ctx = _ctx(_stats({}), _stats({"llm_decode": _program(
        5, 5, 5, {3: (5, 0.02)})}))
    assert manifest.load_reader("decode.paced_ms.sat")(ctx) == \
        pytest.approx(4.0)
    for name in ("prefill.paced_share.sat", "prefill.paced_ms_per_ktok.sat"):
        assert manifest.load_reader(name)(ctx) is None


# ------------------------------------------------------------ the capture

MS = 1_000_000
DECODE, P1K, P4K = "llm_decode", "llm_prefill[1024]", "llm_prefill[4096]"
FINGERPRINT = {DECODE: "jit_fwd(11)", P1K: "jit_fwd(22)", P4K: "jit_fwd(33)"}
TOOK = {DECODE: 10.0, P1K: 40.0, P4K: 150.0}
# the first run is cut by the capture's start (60 of its 150 ms are in
# it), the last one by its end
ORDER = [P4K, DECODE, DECODE, P1K, DECODE, P4K, DECODE, DECODE, P1K,
         DECODE, DECODE]


def _capture(tmp_path, offset_ms, lost=None, tagged=True):
    """``ORDER`` back to back on the device, a sampler run of 0.3 ms
    after each forward; the ``.fetch`` that reads a run's ids ends 0.05
    ms after its sampler, on a host clock ``offset_ms`` ahead.  ``lost``:
    the first run's ``fetch`` (its annotation was open when the capture
    began), its device ``run`` (only the annotation is in the capture),
    or the fetch of run ``lost`` (an event the capture dropped)."""
    from xplane_stats import encode

    modules, ops, host = [], [], []
    t, ordinal = 5.0, {}
    for k, program in enumerate(ORDER):
        took = 60.0 if k == 0 else 6.0 if k == len(ORDER) - 1 \
            else TOOK[program]
        if not (k == 0 and lost == "run"):
            modules.append((FINGERPRINT[program], t * MS, took * MS))
            ops.append((f"%fusion.{k} = bf16[8] fusion(%x), kind=kLoop",
                        t * MS, took * MS))
            if k < len(ORDER) - 1:
                modules.append(("jit_sample_tokens(7)", (t + took) * MS,
                                0.3 * MS))
                ops.append((f"%sort.{k} = s32[16] sort(%y)",
                            (t + took) * MS, 0.3 * MS))
        t += took + 0.3
        run = ordinal[program] = ordinal.get(program, 40) + 1
        if not (k == 0 and lost == "fetch") and k != lost \
                and k < len(ORDER) - 1:
            leaf = "llm.decode.fetch" if k % 3 else "llm.prefill.fetch"
            host.append((leaf, (t - 2.0 + offset_ms) * MS, 2.05 * MS,
                         {"program": program, "run": run} if tagged
                         else {}))
    path = str(tmp_path / "t.xplane.pb")
    with open(path, "wb") as f:
        f.write(encode([
            ("/device:TPU:0", {"XLA Modules": modules, "XLA Ops": ops}),
            ("/host:CPU", {"engine": host})]))
    return {"trace_path": path}


@pytest.mark.parametrize("lost", [None, "fetch", "run"])
@pytest.mark.parametrize("offset_ms", [-1.5, 0.0, 1.5])
def test_runs_are_filed_by_order_whatever_the_clocks_offset(
        tmp_path, offset_ms, lost):
    ctx = _capture(tmp_path, offset_ms, lost)
    value = manifest.load_reader("prefill.device_ms_per_ktok.sat")(ctx)
    # the whole prefills: two of 1,024 at 40 ms, one of 4,096 at 150
    assert value == pytest.approx(1e3 * 230.0 / 6144)
    cap = ctx["info"]["phases"]["run_ledger_capture"]
    assert cap["device_ms_by_bucket"] == pytest.approx(
        {"1024": 40.0, "4096": 150.0})
    assert cap["programs"] == {fp: name for name, fp in FINGERPRINT.items()}
    assert (cap["conflicts"], cap["unfiled"]) == (0, 0)
    # the run the capture's start cut is filed, and dropped (any run at
    # the capture's first operation is: it cannot be known whole); so is
    # the one its end cut
    at_start = DECODE if lost == "run" else P4K
    assert cap["kept"] == {DECODE: 6 - (at_start == DECODE), P1K: 2, P4K: 1}
    assert cap["dropped"] == ({DECODE: 2} if at_start == DECODE
                              else {DECODE: 1, P4K: 1})
    assert cap["decode_ms"] == pytest.approx(10.0)
    assert cap["shift"] == {None: 0, "fetch": 1, "run": -1}[lost]
    offset = cap["clock_offset_ms"]
    assert offset["median"] == pytest.approx(offset_ms + 0.05, abs=0.1)
    assert offset["spread"] == pytest.approx(0.0, abs=1e-6)
    assert offset["pairs"] == 9 if lost else 10
    # the ``run`` tags: every program's ordinals count up by one, and the
    # device holds as many runs of it as the ledger counts between its
    # first and last fetch, give or take the capture's two edges
    assert cap["ordinals"] == {DECODE: [41, 46], P1K: [41, 42],
                               P4K: [42 if lost == "fetch" else 41, 42]}
    assert (cap["fetches_lost"], cap["in_window"]) == (0, False)
    assert cap["runs_less_ordinals"] == {
        DECODE: 1, P1K: 0, P4K: {None: 0, "fetch": 1, "run": -1}[lost]}


def test_a_fetch_the_capture_dropped_is_seen_by_the_ordinals(tmp_path):
    """The ordinals lie among the window's own; and where the eighth run's
    fetch (a decode step's, ordinal 45) is not in the capture, every
    later fetch would pair one run early: the gap is counted, only the
    fetches before it vote, and what they name is filed as ever."""
    ledger = {name: {"runs": n} for name, n in
              ((DECODE, 30), (P1K, 35), (P4K, 41))}
    serve = {"before": {"runs": ledger}, "at_end": {"runs": {
        name: {"runs": run["runs"] + 400} for name, run in ledger.items()}}}
    ctx = {**_capture(tmp_path, 0.5), "serve": serve}
    cap = run_ledger.capture(ctx)
    assert cap["window_ordinals"] == {DECODE: [30, 430], P1K: [35, 435],
                                      P4K: [41, 441]}
    assert (cap["fetches_lost"], cap["in_window"]) == (0, True)
    serve["before"]["runs"][P1K]["runs"] = 42    # the capture began before
    assert run_ledger.capture({**_capture(tmp_path, 0.5), "serve": serve}
                              )["in_window"] is False

    ctx = _capture(tmp_path, 0.5, lost=7)
    value = manifest.load_reader("prefill.device_ms_per_ktok.sat")(ctx)
    assert value == pytest.approx(1e3 * 230.0 / 6144)
    cap = ctx["info"]["phases"]["run_ledger_capture"]
    assert cap["ordinals"][DECODE] == [41, 46]
    assert cap["fetches_lost"] == 1
    assert cap["clock_offset_ms"]["pairs"] == 7
    assert (cap["conflicts"], cap["unfiled"]) == (0, 0)
    assert cap["programs"] == {fp: name for name, fp in FINGERPRINT.items()}


def test_untagged_fetches_file_nothing(tmp_path):
    ctx = _capture(tmp_path, 0.0, tagged=False)
    assert manifest.load_reader("prefill.device_ms_per_ktok.sat")(ctx) is None
    assert run_ledger.capture(ctx) is None


@pytest.mark.parametrize("name,unit,source,layer_of", [
    ("decode.paced_ms.sat", "ms", "program_counter", "decode.device_ms.sat"),
    ("prefill.paced_share.sat", "%", "program_counter",
     "prefill.device_ms.sat"),
    ("prefill.paced_ms_per_ktok.sat", "ms/ktok", "program_counter",
     "prefill.device_ms.sat"),
    ("engine.stall_share.sat", "%", "program_counter", "engine.step_ms.sat"),
    ("prefill.device_ms_per_ktok.sat", "ms/ktok", "program_span",
     "prefill.device_ms.sat")])
def test_the_manifest_lists_them_for_the_serving_cells(name, unit, source,
                                                       layer_of):
    metrics = manifest.load_manifest()["per_layer"]
    entry, = [m for m in metrics if m["name"] == name]
    peer, = [m for m in metrics if m["name"] == layer_of]
    assert entry["workloads"] == peer["workloads"] == CELLS
    assert (entry["layer"], entry["moves"]) == (peer["layer"], peer["moves"])
    assert (entry["unit"], entry["better"], entry["source"]) == \
        (unit, "lower", source)
    assert metrics.index(entry) >= len(metrics) - 5
