"""``collective.reshard_ms`` (PR 40): the device time a traced step spends
in operations whose opcode is ``all-to-all`` or ``collective-permute``
(``-start`` and ``-done`` too), on a trace worked out by hand; 0.0 where
the steps hold none, None where a run has no trace."""
import pytest

from benchmark.harness import manifest, trace as T
from xplane_stats import encode

us = 1000.0     # the trace's times are nanoseconds
FUSION = "%fusion.7 = bf16[8,128]{1,0} fusion(bf16[8,128]{1,0} %p.1), kind=kOutput, calls=%fused_computation.7"
ALL_REDUCE = "%all-reduce.3 = bf16[16,1024,1280]{2,1,0} all-reduce(bf16[16,1024,1280]{2,1,0} %fusion.7), replica_groups={{0,1},{2,3}}, to_apply=%add"
ALL_TO_ALL = "%all-to-all.9 = bf16[1,2,8,1024,640]{3,4,1,2,0:T(8,128)(2,1)} all-to-all(bf16[1,2,8,1024,640]{3,4,1,2,0:T(8,128)(2,1)} %copy.1), replica_groups={{0,1},{2,3}}, dimensions={1}"
ALL_TO_ALL_2 = "%all-to-all.12 = bf16[1,8,1024,2,1920]{2,4,1,0,3:T(8,128)(2,1)} all-to-all(bf16[1,8,1024,2,1920]{2,4,1,0,3:T(8,128)(2,1)} %copy.2), replica_groups={{0,1},{2,3}}, dimensions={3}"
PERMUTE_START = "%collective-permute-start.2 = (bf16[16,1024,1280]{2,1,0:T(8,128)(2,1)}, bf16[16,1024,1280]{2,1,0:T(8,128)(2,1)}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(bf16[16,1024,1280]{2,1,0:T(8,128)(2,1)} %slice.4), source_target_pairs={{0,1},{1,0},{2,3},{3,2}}"
PERMUTE_DONE = "%collective-permute-done.2 = bf16[16,1024,1280]{2,1,0:T(8,128)(2,1)} collective-permute-done((bf16[16,1024,1280]{2,1,0:T(8,128)(2,1)}, bf16[16,1024,1280]{2,1,0:T(8,128)(2,1)}, u32[]{:S(2)}, u32[]{:S(2)}) %collective-permute-start.2)"
TRAIN = {"records": [{"traced": False}, {"traced": True}, {"traced": True}]}


def _trace(tmp_path, reshards: bool):
    """Two chips, two traced steps in a window of 1000 us.  Each chip
    runs, per step, a fusion of 100 us and an all-reduce of 70 us; with
    ``reshards`` also two all-to-alls (30 and 50 us) and a permute whose
    start takes 2 us and whose done 18 us; chip 0's first all-to-all
    takes 10 us more."""
    planes = []
    for chip in (0, 1):
        ops = []
        for step in (0, 1):
            t = step * 500 * us
            run = [(FUSION, 100), (ALL_REDUCE, 70)]
            if reshards:
                run += [(ALL_TO_ALL, 30 + (10 if chip == 0 else 0)),
                        (ALL_TO_ALL_2, 50), (PERMUTE_START, 2),
                        (FUSION, 40), (PERMUTE_DONE, 18)]
            for name, dur in run:
                ops.append((name, t, dur * us))
                t += dur * us
        planes.append((f"/device:TPU:{chip}", {
            "XLA Modules": [("jit_step(1)", 0, 320 * us),
                            ("jit_step(1)", 500 * us, 320 * us)],
            "XLA Ops": ops}))
    planes.append(("/host:CPU", {"python/1": [
        (T.WINDOW_ANNOTATION, 0, 1000 * us)]}))
    path = tmp_path / "reshards.xplane.pb"
    path.write_bytes(encode(planes, {}))
    return str(path)


def test_reshard_ms_is_the_all_to_alls_and_the_permutes_per_step(tmp_path):
    ctx = {"trace_path": _trace(tmp_path, True), "train": TRAIN}
    # per step and chip: 35 + 50 us of all-to-all, 2 + 18 of the permute;
    # the all-reduce (70 us) is no reshard
    assert manifest.load_reader("collective.reshard_ms")(ctx) == \
        pytest.approx(0.105)
    assert ctx["info"]["phases"]["reshard_ms_by_opcode"] == pytest.approx({
        "all-to-all": 0.085, "collective-permute-start": 0.002,
        "collective-permute-done": 0.018})
    assert manifest.load_reader("collective.exposed_ms") is not None


def test_reshard_ms_is_zero_where_a_step_holds_none(tmp_path):
    ctx = {"trace_path": _trace(tmp_path, False), "train": TRAIN}
    assert manifest.load_reader("collective.reshard_ms")(ctx) == 0.0


@pytest.mark.parametrize("ctx", [{}, {"train": TRAIN},
                                 {"trace_path": None, "train": TRAIN}],
                         ids=["nothing", "no_trace", "trace_off"])
def test_reshard_ms_is_left_out_without_a_trace(ctx):
    assert manifest.load_reader("collective.reshard_ms")(ctx) is None


def test_the_manifest_lists_it_for_the_four_chip_cell():
    entry, = [m for m in manifest.load_manifest()["per_layer"]
              if m["name"] == "collective.reshard_ms"]
    peer, = [m for m in manifest.load_manifest()["per_layer"]
             if m["name"] == "collective.exposed_ms"]
    assert entry["workloads"] == peer["workloads"] == \
        ["train-gpt2-large-fsdp2x2"]
    assert (entry["layer"], entry["moves"], entry["source"]) == \
        (peer["layer"], peer["moves"], "device_trace")
    assert (entry["unit"], entry["better"]) == ("ms", "lower")
