"""The reduction from a trace to numbers, on a recorded trace and on one
small enough to work out by hand."""
import os

import pytest

from benchmark.harness import trace as T
from xplane_writer import encode

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "v5e_tiny_gpt2.xplane.pb")
HOST = ("dispatch", "sync", "prefill", "decode")

# An HLO instruction's text, as the XLA Ops line names an event.
FUSION = "%fusion.7 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(bf16[8,128]{1,0} %p.1), kind=kOutput, calls=%fused_computation.7"
KERNEL = '%h_0.4 = bf16[8,256,64]{2,1,0:T(8,128)(2,1)S(1)} custom-call(bf16[8,256,64]{2,1,0} %bitcast.3), custom_call_target="tpu_custom_call"'
ALLRED = "%all-reduce.3 = f32[128]{0:T(128)} all-reduce(f32[128]{0} %x.1), replica_groups={{0,1}}, to_apply=%add"
AG_DONE = "%all-gather-done.2 = bf16[256]{0} all-gather-done((bf16[128]{0}, bf16[256]{0}) %all-gather-start.2)"
WHILE = "%while.2 = (s32[]{:T(128)}, f32[8]{0}) while((s32[], f32[8]{0}) %tuple.1), condition=%cond, body=%body"


def test_op_names_are_read_from_hlo_text():
    assert T.opcode(FUSION) == "fusion" and T.opcode(WHILE) == "while"
    assert T.opcode(KERNEL) == "custom-call" and T.is_kernel(KERNEL)
    assert not T.is_kernel(FUSION)
    assert T.is_collective(ALLRED) and T.is_collective(AG_DONE)
    assert not T.is_collective(FUSION)
    assert T.op_label(FUSION) == "fusion kOutput"
    assert T.op_label(KERNEL) == "h_N custom-call tpu_custom_call"
    assert T.op_label(ALLRED) == "all-reduce"


def test_interval_arithmetic():
    assert T.union([(0, 2), (1, 3), (5, 6), (6, 7)]) == [(0, 3), (5, 7)]
    assert T.subtract([(0, 10)], [(2, 3), (5, 11)]) == [(0, 2), (3, 5)]
    assert T.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert T.total(T.clip([(0, 10), (20, 30)], 5, 25)) == 10


@pytest.fixture
def by_hand(tmp_path):
    """Two chips, a window of 100 us marked by the traced code (times in
    ns).  Chip 0: a while over [10, 50) us holding a fusion [10, 30) and
    the kernel [30, 50); an all-reduce [50, 70) with a fusion [60, 65)
    running inside it; idle [0, 10) and [70, 100).  Chip 1: a fusion
    [0, 40), an all-gather-done [40, 60), idle [60, 100).  The host waits
    in `sync` over [0, 72) us and is in `next_batch` over [72, 100)."""
    us = 1000.0
    planes = [
        ("/device:TPU:0", {
            "XLA Modules": [("jit_step(1)", 10 * us, 60 * us)],
            "XLA Ops": [(WHILE, 10 * us, 40 * us), (FUSION, 10 * us, 20 * us),
                        (KERNEL, 30 * us, 20 * us), (ALLRED, 50 * us, 20 * us),
                        (FUSION, 60 * us, 5 * us)]}),
        ("/device:TPU:1", {
            "XLA Modules": [("jit_step(1)", 0.0, 60 * us)],
            "XLA Ops": [(FUSION, 0.0, 40 * us), (AG_DONE, 40 * us, 20 * us)]}),
        ("/host:CPU", {
            "main/1": [(T.WINDOW_ANNOTATION, 0.0, 100 * us),
                       ("sync", 0.0, 72 * us),
                       ("next_batch", 72 * us, 28 * us)]}),
    ]
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(encode(planes))
    return str(path)


def test_reduction_worked_out_by_hand(by_hand):
    r = T.reduce(T.load(by_hand, ("sync", "next_batch")),
                 ("sync", "next_batch"))
    us = 1e-6
    assert r["devices"] == 2
    assert r["window_s"] == pytest.approx(100 * us)
    # busy: chip 0 [10, 70) = 60 us, chip 1 [0, 60) = 60 us
    assert r["busy_s"] == pytest.approx(60 * us)
    # the kernel ran 20 us on chip 0 and not on chip 1
    assert r["kernel_s"] == pytest.approx(10 * us)
    # collectives: 20 us on each chip; exposed: chip 0 [50, 60) + [65, 70)
    # = 15 us (a fusion overlaps 5 us of it), chip 1 all 20 us
    assert r["collective_s"] == pytest.approx(20 * us)
    assert r["exposed_collective_s"] == pytest.approx(17.5 * us)
    assert r["programs"]["jit_step"]["jit_step(1)"] == \
        pytest.approx([60 * us, 60 * us])
    # the while is a parent: its time is its children's
    ops = dict(r["device_ops"])
    assert "while" not in ops
    assert ops["fusion kOutput"] == pytest.approx((25 + 40) / 2 * us)
    assert ops["h_N custom-call tpu_custom_call"] == pytest.approx(10 * us)
    # idle: chip 0 [0, 10) + [70, 72) under sync, [72, 100) under
    # next_batch; chip 1 [60, 72) under sync, [72, 100) next_batch... a gap
    # goes to the span that covers most of it: [70, 100) and [60, 100) are
    # mostly next_batch, [0, 10) is sync.
    gaps = dict(r["idle_gaps"])
    assert gaps["sync"] == pytest.approx(10 / 2 * us)
    assert gaps["next_batch"] == pytest.approx((30 + 40) / 2 * us)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_recorded_v5e_trace():
    """A trace recorded on one v5e chip (benchmark/tools/probe_trace.py
    tiny: three train steps of a 2-layer GPT-2 with the flash kernel, one
    prefill and three decode forwards of the engine), trimmed to the lines
    the reduction reads.  Checked against sums made the plain way."""
    from jax.profiler import ProfileData

    plane = next(p for p in ProfileData.from_file(RECORDED).planes
                 if p.name == "/device:TPU:0")
    lines = {ln.name: [(e.name, e.start_ns, e.duration_ns)
                       for e in ln.events] for ln in plane.lines}
    ops = lines["XLA Ops"]
    lo = min(s for _, s, _ in ops)
    hi = max(s + d for _, s, d in ops)
    # busy, the plain way: mark every nanosecond-interval boundary
    edges = sorted({s for _, s, _ in ops} | {s + d for _, s, d in ops})
    busy = sum(b - a for a, b in zip(edges, edges[1:])
               if any(s <= a and b <= s + d for _, s, d in ops
                      if s < b and s + d > a))
    kernels = [d for n, _, d in ops if "tpu_custom_call" in n]

    r = T.reduce(T.load(RECORDED, HOST), HOST)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert r["busy_s"] == pytest.approx(busy / 1e9, rel=1e-9)
    assert 0 < r["busy_s"] < r["window_s"]
    # 2 layers x (forward, dq, dkv) x 3 steps
    assert len(kernels) == 18
    assert r["kernel_s"] == pytest.approx(sum(kernels) / 1e9)
    assert r["exposed_collective_s"] == 0.0
    steps = r["programs"]["jit_step"]
    assert [len(v) for v in steps.values()] == [3]
    assert sum(sum(v) for v in steps.values()) == pytest.approx(
        sum(d for n, _, d in lines["XLA Modules"]
            if n.startswith("jit_step")) / 1e9)
    decode, prefill = T.split_decode_prefill(r)
    assert len(decode) == 3 and len(prefill) == 1
    assert r["device_ops"][0][0] == "h_N custom-call tpu_custom_call"
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"])
