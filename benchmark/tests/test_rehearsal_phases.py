"""The serving and the training cell rehearsed on the CPU with --trace 1
(rehearse_run.py, as test_rehearsal.py does): each reports the metrics of
PR 24 that a CPU run can.  A rehearsal's line says platform "cpu": it is no
measurement.  What needs a TPU's trace (the kernels' names, the scopes'
tf_op, jit_fwd runs on a device plane) is checked on the recorded trace in
test_phases.py."""
import json

import pytest

import rehearsal


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearsal.build(str(tmp_path_factory.mktemp("bench_phases")))


def _lines(out):
    assert out.returncode == 0, out.stderr[-3000:]
    info, line = out.stdout.strip().splitlines()[-2:]
    return json.loads(info)["info"], json.loads(line)


def test_serving_cell_reports_the_engines_parts(root):
    info, line = _lines(rehearsal.run_cell(root, "tiny-sat", trace=1))
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert {"engine.sample_ms.sat", "engine.schedule_ms.sat",
            "engine.device_wait_ms.sat", "engine.idle_host_ms.sat",
            "engine.idle_fetch_ms.sat"} <= set(m)
    parts = m["engine.sample_ms.sat"] + m["engine.schedule_ms.sat"] \
        + m["engine.device_wait_ms.sat"]
    assert parts == pytest.approx(m["engine.step_ms.sat"], rel=0.02)
    phases = info["detail"]["phases"]
    assert set(phases["engine_step_ms"]["leaves"]) == \
        {"llm.cancel", "llm.admit", "llm.prefill.pack", "llm.prefill.run",
         "llm.prefill.fetch", "llm.prefill.sample", "llm.decode.pages",
         "llm.decode.pack", "llm.decode.run", "llm.decode.fetch",
         "llm.decode.sample", "llm.publish", "llm.other"}
    assert phases["engine_step_ms"]["compiles"] == 0
    cap = phases["capture"]
    assert (m["engine.idle_host_ms.sat"] + m["engine.idle_fetch_ms.sat"]) \
        * cap["steps"] == pytest.approx(1e3 * cap["idle_s"])
    assert line["correct"] is True


@pytest.mark.parametrize("cell,chips", [("tiny-train", 1),
                                        ("tiny-train-2x2", 4)])
def test_training_cell_reports_the_trainers_report(root, cell, chips):
    info, line = _lines(rehearsal.run_cell(root, cell, chips=chips,
                                           devices=chips, trace=1))
    assert line["metrics"]["trainer.report_ms"]["value"] > 0
    cap = info["detail"]["phases"]["capture"]
    assert cap["report_push_ms"] > cap["report_observe_ms"] > 0
    assert cap["dispatch_ms"] > 0
    assert line["correct"] is True
