"""benchmark/harness/phases.py: the second pass over a trace with the
program's own names (PR 24), on a trace worked out by hand, on one recorded
on a v5e chip in that PR's probe call, and on a trace of the parent's kind,
which carries none of the names."""
import json
import os

import pytest

from benchmark.harness import manifest, phases as P, trace as T
from xplane_stats import encode

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "v5e_tiny_phases.xplane.pb")
RECORDED_STATS = os.path.join(DATA, "v5e_tiny_phases.stats.json")
PARENT_KIND = os.path.join(DATA, "v5e_tiny_gpt2.xplane.pb")
NEW_METRICS = (
    "engine.sample_ms.sat", "engine.schedule_ms.sat",
    "engine.device_wait_ms.sat", "engine.idle_host_ms.sat",
    "engine.idle_fetch_ms.sat", "prefill.device_ms.sat",
    "kernel.flash_fwd_ms", "kernel.flash_dq_ms", "kernel.flash_dkv_ms",
    "step.loss_ms", "step.optimizer_ms", "trainer.report_ms")
us = 1000.0     # the trace's times are nanoseconds

FUSION = "%fusion.7 = bf16[8,128]{1,0} fusion(bf16[8,128]{1,0} %p.1), kind=kOutput, calls=%fused_computation.7"
FUSION_OPT = "%fusion.9 = f32[128]{0} fusion(f32[128]{0} %p.2), kind=kLoop, calls=%fused_computation.9"
COPY = "%copy.4 = bf16[8,128]{0,1} copy(bf16[8,128]{1,0} %fusion.7)"
COPY_BARE = "%copy.5 = f32[128]{0} copy(f32[128]{0} %p.3)"
KERNEL_FWD = '%flash_fwd.4 = bf16[8,256,64]{2,1,0} custom-call(bf16[8,256,64]{2,1,0} %bitcast.3), custom_call_target="tpu_custom_call"'
KERNEL_DKV = '%flash_dkv.2 = bf16[8,256,64]{2,1,0} custom-call(bf16[8,256,64]{2,1,0} %bitcast.5), custom_call_target="tpu_custom_call"'
# a kernel whose instruction took another name: found by its scope
KERNEL_DQ = '%shard_map.3 = bf16[8,256,64]{2,1,0} custom-call(bf16[8,256,64]{2,1,0} %bitcast.4), custom_call_target="tpu_custom_call"'
SCOPES = {
    FUSION: "jit(step)/loss_and_grad/jvp(loss)/while/body/dot_general",
    FUSION_OPT: "jit(step)/optimizer/add",
    COPY: "jit(step)/loss_and_grad/transpose(jvp(GPT2))/loss_and_grad/"
          "jvp(GPT2)/checkpoint/h_0/attn.qkv/split",
    KERNEL_FWD: "jit(step)/loss_and_grad/jvp(GPT2)/h_0/attn.core/"
                "flash_fwd/flash_fwd/pallas_call:",
    KERNEL_DKV: "jit(step)/loss_and_grad/transpose(jvp(GPT2))/h_0/"
                "attn.core/flash_dkv/flash_dkv/pallas_call:",
    KERNEL_DQ: "jit(step)/loss_and_grad/transpose(jvp(GPT2))/h_0/"
               "attn.core/shard_map/flash_dq/flash_dq/pallas_call:",
}


def test_scope_paths_are_read_through_the_transformations():
    path = SCOPES[COPY]
    assert P.scope_parts(path)[:3] == ["step", "loss_and_grad", "GPT2"]
    assert P.scope_parts("a/transpose(jvp(loss))/pallas_call:") == \
        ["a", "loss", "pallas_call"]
    assert P.filed_under(path) == "attn.qkv"
    assert P.filed_under(SCOPES[FUSION]) == "loss"
    assert P.filed_under(SCOPES[KERNEL_DQ]) == "attn.core"
    assert P.filed_under("jit(step)/loss_and_grad/mul") == \
        "loss_and_grad (other)"
    assert P.filed_under("jit(fwd)/h_3/ln_2/mul") == "ln_2"
    assert P.filed_under(None) == "(no scope)"


# --------------------------------------------------------------- serving

@pytest.fixture
def serving_by_hand(tmp_path):
    """One chip, a window of 1000 us, two engine steps.

    Step A [0, 500): cancel [0, 10), admit [10, 50) with no prefill, decode
    [50, 480) = pages [50, 60), pack [60, 80), run [80, 100), fetch
    [100, 400), sample [400, 470); publish [480, 490).  The decode program
    D runs on the device over [90, 300).
    Step B [500, 1000): admit [500, 800) holding three prefills of bucket
    32, the k-th over [500 + 100k, 590 + 100k) = run 20 us then fetch 70 us,
    its program P on the device over [510 + 100k, 560 + 100k); decode
    [800, 990) = run [800, 820), fetch [820, 950), sample [950, 990), D on
    the device over [810, 940).
    So P is the jit_fwd run most often (3 against 2)."""
    host = [(T.WINDOW_ANNOTATION, 0, 1000 * us),
            ("llm.step", 0, 500 * us, {"step": 7, "running": 1}),
            ("llm.cancel", 0, 10 * us), ("llm.admit", 10 * us, 40 * us),
            ("llm.decode", 50 * us, 430 * us, {"batch": 1}),
            ("llm.decode.pages", 50 * us, 10 * us),
            ("llm.decode.pack", 60 * us, 20 * us),
            ("llm.decode.run", 80 * us, 20 * us),
            ("llm.decode.fetch", 100 * us, 300 * us),
            ("llm.decode.sample", 400 * us, 70 * us),
            ("llm.publish", 480 * us, 10 * us),
            ("llm.step", 500 * us, 500 * us, {"step": 8, "running": 1}),
            ("llm.admit", 500 * us, 300 * us),
            ("llm.decode", 800 * us, 190 * us, {"batch": 4}),
            ("llm.decode.run", 800 * us, 20 * us),
            ("llm.decode.fetch", 820 * us, 130 * us),
            ("llm.decode.sample", 950 * us, 40 * us)]
    modules, ops = [("jit_fwd(1)", 90 * us, 210 * us),
                    ("jit_fwd(1)", 810 * us, 130 * us)], \
        [(FUSION, 90 * us, 210 * us), (FUSION, 810 * us, 130 * us)]
    for k in range(3):
        s = (500 + 100 * k) * us
        host += [("llm.prefill", s, 90 * us,
                  {"seq": k, "prompt_tokens": 20, "bucket": 32}),
                 ("llm.prefill.run", s, 20 * us),
                 ("llm.prefill.fetch", s + 20 * us, 70 * us)]
        modules.append(("jit_fwd(2)", s + 10 * us, 50 * us))
        ops.append((FUSION, s + 10 * us, 50 * us))
    path = tmp_path / "serving.xplane.pb"
    path.write_bytes(encode([
        ("/device:TPU:0", {"XLA Modules": modules, "XLA Ops": ops}),
        ("/host:CPU", {"llm-engine/1": host})]))
    return str(path)


def test_serving_gaps_and_runs_worked_out_by_hand(serving_by_hand):
    ctx = {"trace_path": serving_by_hand}
    cap = P.serve_capture(ctx)
    assert cap["steps"] == pytest.approx(2.0)
    # idle: [0,90) [300,510) [560,610) [660,710) [760,810) [940,1000)
    assert cap["idle_s"] == pytest.approx(510e-6)
    by_leaf = {k: v * cap["steps"] for k, v in
               cap["idle_ms_by_leaf"].items()}      # us x 1e-3, both steps
    assert by_leaf == pytest.approx({
        "llm.cancel": 0.010, "llm.admit": 0.070,   # its prefills apart
        "llm.decode.pages": 0.010, "llm.decode.pack": 0.020,
        "llm.decode.run": 0.020, "llm.decode.fetch": 0.110,
        "llm.decode.sample": 0.110, "llm.publish": 0.010,
        "llm.prefill.run": 0.030, "llm.prefill.fetch": 0.090,
        "(no leaf)": 0.030})
    # under .run / .fetch: 20 + 110 + 30 + 90 = 250 us; the rest 260 us
    assert cap["idle_fetch_ms"] == pytest.approx(0.125)
    assert cap["idle_host_ms"] == pytest.approx(0.130)
    assert (cap["idle_fetch_ms"] + cap["idle_host_ms"]) * cap["steps"] \
        == pytest.approx(1e3 * cap["idle_s"])
    # the clock tells decode from prefill; the most-often rule takes P
    assert cap["decode_runs"] == 2 and cap["unclassed_runs"] == 0
    assert cap["decode_ms"] == pytest.approx(0.170)
    assert cap["prefill_runs"] == {"32": 3}
    assert cap["prefill_ms_by_bucket"] == {"32": pytest.approx(0.050)}
    most_often, _ = T.split_decode_prefill(
        T.reduce(T.load(serving_by_hand)))
    assert len(most_often) == 3
    read = manifest.load_reader("prefill.device_ms.sat")
    assert read(ctx) == pytest.approx(0.050)
    assert manifest.load_reader("engine.idle_host_ms.sat")(ctx) == \
        pytest.approx(0.130)
    assert ctx["info"]["phases"]["capture"] is cap


def test_a_run_no_annotation_covers_goes_with_its_program(tmp_path):
    """The step under way when a capture starts: its decode run over
    [0, 40) us has no annotation; the next step's, [110, 150), lies in an
    llm.decode.  A program seen under no annotation at all stays
    unclassed."""
    path = tmp_path / "edge.xplane.pb"
    path.write_bytes(encode([
        ("/device:TPU:0", {
            "XLA Modules": [("jit_fwd(1)", 0, 40 * us),
                            ("jit_fwd(9)", 50 * us, 10 * us),
                            ("jit_fwd(1)", 110 * us, 40 * us)],
            "XLA Ops": [(FUSION, 0, 40 * us), (FUSION, 50 * us, 10 * us),
                        (FUSION, 110 * us, 40 * us)]}),
        ("/host:CPU", {"llm-engine/1": [
            ("llm.step", 100 * us, 100 * us),
            ("llm.decode", 100 * us, 90 * us, {"batch": 1})]})]))
    cap = P.serve_capture({"trace_path": str(path)})
    assert cap["decode_runs"] == 2 and cap["unclassed_runs"] == 1
    assert cap["decode_ms"] == pytest.approx(0.040)


def test_engine_counters_split_a_step_into_three_parts():
    leaves = dict.fromkeys(P.LLM_LEAVES, 0.0)
    before = {"steps": 10, "step_s": 1.0, "phase_s": dict(leaves),
              "prefills": 2, "compiles": 9}
    after = {"steps": 20, "step_s": 2.7, "prefills": 5, "compiles": 9,
             "phase_s": dict(leaves, **{
                 "llm.decode.sample": 0.20, "llm.prefill.sample": 0.02,
                 "llm.decode.run": 0.05, "llm.decode.fetch": 1.00,
                 "llm.prefill.run": 0.03, "llm.prefill.fetch": 0.12,
                 "llm.cancel": 0.01, "llm.admit": 0.02,
                 "llm.decode.pages": 0.03, "llm.decode.pack": 0.04,
                 "llm.prefill.pack": 0.05, "llm.publish": 0.06})}
    ctx = {"serve": {"before": before, "at_end": after}}
    split = P.engine_split_ms(ctx)
    # llm.other: 1.7 s of steps less 1.63 s of leaves = 0.07 s
    assert split == pytest.approx(
        {"sample": 22.0, "device_wait": 120.0, "schedule": 28.0})
    assert sum(split.values()) == pytest.approx(170.0)
    info = ctx["info"]["phases"]["engine_step_ms"]
    assert info["leaves"]["llm.other"] == pytest.approx(7.0)
    assert info["prefills"] == 3 and info["compiles"] == 0
    for name, want in (("engine.sample_ms.sat", 22.0),
                       ("engine.schedule_ms.sat", 28.0),
                       ("engine.device_wait_ms.sat", 120.0)):
        assert manifest.load_reader(name)(ctx) == pytest.approx(want)


# -------------------------------------------------------------- training

@pytest.fixture
def training_by_hand(tmp_path):
    """Two chips, two traced steps in a window of 1000 us.  Each chip
    runs, per step: the forward kernel 40 us, the dq kernel (its
    instruction named after shard_map) 50 us, the dkv kernel 60 us, a
    loss fusion 100 us, an optimizer fusion 20 us, a copy under attn.qkv
    30 us; chip 0 also a copy with no scope, 10 us a step.  train.report
    takes 8 us and 12 us: observe 1 us, push 6 us and 10 us."""
    planes = []
    for chip in (0, 1):
        ops = []
        for step in (0, 1):
            t = step * 500 * us
            for name, dur in ((KERNEL_FWD, 40), (KERNEL_DQ, 50),
                              (KERNEL_DKV, 60), (FUSION, 100),
                              (FUSION_OPT, 20), (COPY, 30)):
                ops.append((name, t, dur * us))
                t += dur * us
            if chip == 0:
                ops.append((COPY_BARE, t, 10 * us))
        planes.append((f"/device:TPU:{chip}", {
            "XLA Modules": [("jit_step(1)", 0, 310 * us),
                            ("jit_step(1)", 500 * us, 310 * us)],
            "XLA Ops": ops}))
    planes.append(("/host:CPU", {"python/1": [
        (T.WINDOW_ANNOTATION, 0, 1000 * us),
        ("train.report", 400 * us, 8 * us),
        ("train.report.observe", 400 * us, 1 * us),
        ("train.report.push", 402 * us, 6 * us),
        ("train.report", 900 * us, 12 * us),
        ("train.report.observe", 900 * us, 1 * us),
        ("train.report.push", 902 * us, 10 * us)]}))
    path = tmp_path / "training.xplane.pb"
    path.write_bytes(encode(planes, {k: {"tf_op": v}
                                     for k, v in SCOPES.items()}))
    return str(path)


def test_training_kernels_scopes_and_report_by_hand(training_by_hand):
    ctx = {"trace_path": training_by_hand, "train": {
        "records": [{"traced": False}, {"traced": True}, {"traced": True}]}}
    cap = P.train_capture(ctx)
    assert (cap["steps"], cap["devices"], cap["scoped"]) == (2, 2, True)
    assert cap["kernel_ms"] == pytest.approx(
        {"flash_fwd": 0.040, "flash_dq": 0.050, "flash_dkv": 0.060})
    reduced = T.reduce(T.load(training_by_hand))
    assert sum(cap["kernel_ms"].values()) == pytest.approx(
        1e3 * reduced["kernel_s"] / cap["steps"])
    assert cap["loss_ms"] == pytest.approx(0.100)
    assert cap["optimizer_ms"] == pytest.approx(0.020)
    assert cap["ms_by_scope"] == pytest.approx({
        "attn.core": 0.150, "loss": 0.100, "attn.qkv": 0.030,
        "optimizer": 0.020, "(no scope)": 0.005})
    assert cap["copy_ms_by_scope"] == pytest.approx(
        {"attn.qkv": 0.030, "(no scope)": 0.005})
    assert cap["report_ms"] == pytest.approx(0.010)
    assert cap["report_observe_ms"] == pytest.approx(0.001)
    assert cap["report_push_ms"] == pytest.approx(0.008)
    for name, want in (("kernel.flash_fwd_ms", 0.040),
                       ("kernel.flash_dq_ms", 0.050),
                       ("kernel.flash_dkv_ms", 0.060),
                       ("step.loss_ms", 0.100),
                       ("step.optimizer_ms", 0.020),
                       ("trainer.report_ms", 0.010)):
        assert manifest.load_reader(name)(ctx) == pytest.approx(want), name


# ------------------------------------------------- recorded on a v5e chip

def test_recorded_v5e_trace_with_the_programs_names():
    """PR 24's probe call: in ONE capture two train steps of a 2-layer
    GPT-2 (256 wide, flash kernels, chunked loss) through
    make_sharded_train_step and session.report, then the engine's thread
    serving five prompts of bucket 16 with one token each and two more
    (buckets 8 and 32) for three decode steps; trimmed (xplane_stats.py)
    to what phases.py reads.  The prefill of bucket 16 is the jit_fwd
    run most often."""
    with open(RECORDED_STATS) as f:
        stats = json.load(f)
    ctx = {"trace_path": RECORDED,
           "train": {"records": [{"traced": True}] * 2},
           "serve": {"before": stats["before"], "at_end": stats["after"]}}
    reduced = T.reduce(T.load(RECORDED))
    train = P.train_capture(ctx)
    assert set(train["kernel_ms"]) == set(P.KERNELS)
    assert sum(train["kernel_ms"].values()) * 2 == pytest.approx(
        1e3 * reduced["kernel_s"])
    assert train["scoped"] and train["loss_ms"] > 0 \
        and train["optimizer_ms"] > 0
    assert {"attn.core", "mlp", "loss", "optimizer", "embed"} <= \
        set(train["ms_by_scope"])
    assert train["report_ms"] > train["report_observe_ms"] > 0
    assert train["report_push_ms"] is None      # a session with no queue

    serve = P.serve_capture(ctx)
    assert serve["decode_runs"] == 3 and serve["unclassed_runs"] == 0
    assert serve["prefill_runs"] == {"16": 5, "32": 1, "8": 1}
    most_often, _ = T.split_decode_prefill(reduced)
    assert len(most_often) == 5         # the old rule takes the prefill
    assert (serve["idle_host_ms"] + serve["idle_fetch_ms"]) \
        * serve["steps"] == pytest.approx(
            1e3 * (reduced["window_s"] - reduced["busy_s"]))
    split = P.engine_split_ms(ctx)
    steps = stats["after"]["steps"] - stats["before"]["steps"]
    assert sum(split.values()) == pytest.approx(
        1e3 * (stats["after"]["step_s"] - stats["before"]["step_s"])
        / steps)


# ------------------------------------------- a program without the names

@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_parent_without_the_names_gives_nothing_and_raises_nothing(
        metric, capsys):
    """PR 23's recorded trace: kernels named %h_N, no llm.* or train.*
    annotation, no tf_op; and stats() without phase_s."""
    plain = {"steps": 5, "tokens_generated": 9}
    ctx = {"trace_path": PARENT_KIND,
           "train": {"records": [{"traced": True}] * 3},
           "serve": {"before": plain, "at_end": dict(plain, steps=9)}}
    assert manifest.load_reader(metric)(ctx) is None
    assert "failed" not in capsys.readouterr().err


def test_a_reader_that_breaks_is_reported_and_leaves_its_metric_out(capsys):
    assert manifest.load_reader("kernel.flash_fwd_ms")(
        {"trace_path": "/nonexistent.xplane.pb",
         "train": {"records": [{"traced": True}]}}) is None
    assert "reader read failed" in capsys.readouterr().err
