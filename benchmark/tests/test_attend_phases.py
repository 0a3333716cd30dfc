"""benchmark/harness/attend_phases.py: which operations are filed under
the decode attention, the bytes behind its roofline share, and that a
program without the scope or the counter (the parent of PR 29) gives
every reader nothing to read."""
import pytest

from benchmark.harness import attend_phases as A, peaks

KERNEL = ('%paged_decode.7 = bf16[16,1,1280]{2,1,0} custom-call(s32[1]{0} '
          '%layer), custom_call_target="tpu_custom_call"')
GATHER = "%fusion.3 = bf16[16,64,16,1280]{3,2,1,0} fusion(%p), kind=kCustom"
MATMUL = "%fusion.9 = bf16[16,3840]{1,0} fusion(%x), kind=kOutput"


@pytest.mark.parametrize("op,scope,filed", [
    (KERNEL, None, True),                    # by its instruction's name
    (GATHER, "jit(fwd)/GPT2/h_3/attn.core/kv.attend/gather", True),
    (GATHER, "jit(fwd)/GPT2/h_3/attn.core/kv.store/scatter", False),
    (MATMUL, "jit(fwd)/GPT2/h_3/attn.qkv/dot_general", False),
    (MATMUL, None, False)])
def test_what_is_filed_under_the_decode_attention(op, scope, filed):
    assert A._is_attend(op, scope) is filed


def _ctx(before, at_end):
    return {"serve": {"before": before, "at_end": at_end},
            "peaks": peaks.peaks_for("TPU v5 lite")}


def test_roofline_counts_the_rows_read_never_the_rows_held():
    counts = {"decode_runs": 10, "kv_rows_read": 1000, "kv_rows_held": 4000,
              "kv_row_bytes": 5120}
    later = {"decode_runs": 110, "kv_rows_read": 1000 + 100 * 200_000,
             "kv_rows_held": 4000 + 100 * 589_824, "kv_row_bytes": 5120}
    ctx = _ctx({"attention": counts}, {"attention": later})
    rows = A.rows(ctx)
    assert rows["kv_rows_read"] == 200_000
    assert rows["read_over_held"] == pytest.approx(200_000 / 589_824)
    ctx["_attend_capture"] = {"attend_ms": 2.5}
    roof = A.attend_roofline(ctx)
    # 200,000 rows x 5,120 bytes = 1.024 GB: 1.25 ms at 819 GB/s.
    assert roof["bytes"] == 200_000 * 5120
    assert roof["pct"] == pytest.approx(100 * 1.2503 / 2.5, rel=1e-3)


def test_a_program_without_the_counter_gives_nothing_to_read():
    ctx = _ctx({"steps": 1}, {"steps": 9})
    ctx["_attend_capture"] = {"attend_ms": 25.9}
    assert A.rows(ctx) is None and A.attend_roofline(ctx) is None
    assert A.capture({"trace": None}) is None
