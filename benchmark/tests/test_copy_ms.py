"""``step.copy_ms`` (PR 36): the device time a traced step spends in
operations whose opcode is ``copy``, the sum of the training capture's
``copy_ms_by_scope``, from a trace worked out by hand
(``test_phases.training_by_hand``); None where a run has no trace, 0.0
where its steps hold no such operation."""
import pytest

from benchmark.harness import manifest, phases as P
from test_phases import training_by_hand  # noqa: F401 — the fixture

TRAIN = {"records": [{"traced": False}, {"traced": True}, {"traced": True}]}


def test_copy_ms_is_the_sum_of_the_copies_by_scope(training_by_hand):  # noqa: F811
    ctx = {"trace_path": training_by_hand, "train": TRAIN}
    read = manifest.load_reader("step.copy_ms")
    by_scope = P.train_capture(ctx)["copy_ms_by_scope"]
    # per step and chip: 30 us under attn.qkv, 10 us on one chip of two
    assert by_scope == pytest.approx({"attn.qkv": 0.030, "(no scope)": 0.005})
    assert read(ctx) == pytest.approx(0.035)
    assert read(ctx) == pytest.approx(sum(by_scope.values()))


@pytest.mark.parametrize("ctx", [{}, {"train": TRAIN},
                                 {"trace_path": None, "train": TRAIN}],
                         ids=["nothing", "no_trace", "trace_off"])
def test_copy_ms_is_left_out_without_a_trace(ctx):
    assert manifest.load_reader("step.copy_ms")(ctx) is None


def test_the_manifest_lists_it_for_the_training_cells():
    entry, = [m for m in manifest.load_manifest()["per_layer"]
              if m["name"] == "step.copy_ms"]
    peer, = [m for m in manifest.load_manifest()["per_layer"]
             if m["name"] == "step.device_ms"]
    assert entry["workloads"] == peer["workloads"]
    assert (entry["layer"], entry["moves"], entry["source"]) == \
        (peer["layer"], peer["moves"], "device_trace")
    assert (entry["unit"], entry["better"]) == ("ms", "lower")
