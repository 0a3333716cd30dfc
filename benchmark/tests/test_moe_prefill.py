"""``moe.prefill_ms.sat`` and ``moe.compact_share.sat`` (PR 43) on the
capture tests/test_olmoe.py makes by hand (two decode runs and one prefill
run of ``jit_fwd``, the same operations in each) and on counters written
out; None where a program has no ``moe.*`` names, or no
``stats()["moe_prefill"]`` (the parent of PR 43)."""
import pytest

from benchmark.harness import manifest
from test_olmoe import _ctx

CELLS = ["serve-olmoe-1b-7b-sat", "serve-granite-4.0-h-small-sat",
         "serve-lfm2-24b-a2b-sat", "serve-kimi-k2.5-4k",
         "serve-kimi-linear-48b-a3b-longout"]


def test_prefill_ms_is_the_prefill_runs_moe_time(tmp_path):
    ctx = _ctx(tmp_path, [("moe.route", 1), ("moe.dispatch", 0.5),
                          ("moe.experts", 1), ("moe.combine", 2),
                          ("mlp_in", 7)],
               kernels=[("ragged-dot-metadata", 0.5),
                        ("ragged-dot-none", 4), ("ragged-dot-none", 5)])
    # the one prefill run's: the two decode runs hold the same operations
    assert manifest.load_reader("moe.prefill_ms.sat")(ctx) == \
        pytest.approx(14.0)
    note = ctx["info"]["phases"]["moe_prefill_capture"]
    assert note["prefill_runs"] == 1
    # a prefill run's ``ragged-dot-none`` is the experts', its metadata
    # kernel the dispatch's
    assert note["ms_by_scope"] == pytest.approx({
        "moe.route": 1.0, "moe.dispatch": 1.0, "moe.experts": 10.0,
        "moe.combine": 2.0})
    assert note["ms_by_bucket"] == pytest.approx({"32": 14.0})
    # the accepted decode reader beside it is not moved by it
    assert manifest.load_reader("moe.experts_ms.sat")(ctx) == \
        pytest.approx(10.0)


def test_prefill_ms_is_left_out_without_the_names(tmp_path):
    assert manifest.load_reader("moe.prefill_ms.sat")(
        _ctx(tmp_path, [("mlp_in", 5)])) is None


@pytest.mark.parametrize("ctx", [{}, {"trace_path": None}],
                         ids=["nothing", "trace_off"])
def test_prefill_ms_is_left_out_without_a_trace(ctx):
    assert manifest.load_reader("moe.prefill_ms.sat")(ctx) is None


def _counters(before, at_end):
    return {"serve": {"before": before, "at_end": at_end}}


def test_compact_share_is_compact_over_layer_runs_of_the_window():
    a = {"layer_runs": 12, "pairs": 4000, "compact": 6}
    b = {"layer_runs": 12 + 60, "pairs": 4000 + 36000, "compact": 6 + 57}
    ctx = _counters({"moe_prefill": a}, {"moe_prefill": b})
    assert manifest.load_reader("moe.compact_share.sat")(ctx) == \
        pytest.approx(95.0)
    assert ctx["info"]["phases"]["moe_prefill_routing"] == {
        "layer_runs": 60, "compact": 57, "pairs_per_layer_run": 600.0}
    whole = _counters({"moe_prefill": a},
                      {"moe_prefill": dict(b, compact=6)})
    assert manifest.load_reader("moe.compact_share.sat")(whole) == 0.0


@pytest.mark.parametrize("ctx", [
    {}, _counters({}, {}),
    _counters({"moe": {"layer_runs": 1}}, {"moe": {"layer_runs": 9}}),
    _counters({"moe_prefill": {"layer_runs": 4, "pairs": 9, "compact": 4}},
              {"moe_prefill": {"layer_runs": 4, "pairs": 9, "compact": 4}})],
    ids=["nothing", "dense", "the_parent", "no_prefill_in_the_window"])
def test_compact_share_is_left_out_without_the_counter(ctx):
    assert manifest.load_reader("moe.compact_share.sat")(ctx) is None


@pytest.mark.parametrize("name,unit,better,source", [
    ("moe.prefill_ms.sat", "ms", "lower", "device_trace"),
    ("moe.compact_share.sat", "%", "higher", "program_counter")])
def test_the_manifest_lists_them_for_the_cells_with_experts(
        name, unit, better, source):
    metrics = manifest.load_manifest()["per_layer"]
    entry, = [m for m in metrics if m["name"] == name]
    peer, = [m for m in metrics if m["name"] == "moe.experts_ms.sat"]
    assert entry["workloads"] == peer["workloads"] == CELLS
    assert (entry["layer"], entry["moves"]) == (peer["layer"], peer["moves"])
    assert (entry["unit"], entry["better"], entry["source"]) == \
        (unit, better, source)
