"""XLA performance introspection plane (ISSUE 16).

Fast half: the jax/aiohttp-free import guard for ``util/xprof.py`` +
the ``rt perf`` CLI parser (an ops box without the ML deps must render
a perf report from telemetry), then pure units for the roofline math,
both HLO replica-group syntaxes, collective-to-mesh-axis attribution,
wire-byte conventions, report assembly/rendering, the telemetry
``xla`` aggregation, and the doctor's recompile-churn / device-memory
finders.  One subprocess test compiles a real sharded train step over
a 4-virtual-device fsdp x tensor mesh and asserts the harvested
collectives land nonzero bytes on BOTH axes.

Slow half: the automated step decomposition's structure (optimizer
~free, backward over forward; of-peak ratios only judged on a real
accelerator).
"""

import os
import subprocess
import sys
import textwrap

import pytest

from ray_tpu.util import xprof

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -------------------------------------------------- import guard
def test_xprof_and_perf_cli_import_without_jax_or_aiohttp():
    """util/xprof.py's pure layer, the state API, and the `rt perf`
    parser must import AND compute on a box with neither jax nor
    aiohttp — `rt perf` is an ops-box tool over telemetry data."""
    script = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {REPO!r})

        class _Block:
            BLOCKED = ("jax", "aiohttp", "flax", "optax")
            def find_module(self, name, path=None):
                root = name.split(".")[0]
                return self if root in self.BLOCKED else None
            def load_module(self, name):
                raise ImportError(f"blocked import: {{name}}")

        sys.meta_path.insert(0, _Block())
        for mod in ("jax", "aiohttp"):
            assert mod not in sys.modules

        from ray_tpu.util import xprof
        from ray_tpu.util import state  # noqa: F401
        from ray_tpu.scripts import cli

        parser = cli._build_parser()
        for args in (["perf"], ["perf", "--json"],
                     ["perf", "--format", "json"]):
            ns = parser.parse_args(args)
            assert callable(ns.fn)

        # Pure compute path: HLO parse -> attribution -> report.
        hlo = '''
          %ar = f32[4,16]{{1,0}} all-reduce(%x), replica_groups={{{{0,1}},{{2,3}}}}
        '''
        colls = xprof.parse_hlo_collectives(hlo)
        assert colls and colls[0]["op"] == "all-reduce"
        summary = xprof.summarize_collectives(
            colls, {{"fsdp": 2, "tensor": 2}})
        assert summary["tensor"]["bytes"] > 0
        rep = xprof.build_report(
            {{"train_step": {{"flops": 1e12, "bytes": 1e9,
                              "collectives": summary,
                              "compiles": 1,
                              "compile_seconds": 2.0}}}},
            {{"train_step": {{"step_time_s": 0.1}}}},
            peak_flops=100e12, peak_hbm=1e12, interconnect=100e9)
        text = xprof.render_report(rep)
        assert "train_step" in text and "roofline" in text
        print("GUARD_OK")
    """)
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=120)
    assert "GUARD_OK" in out.stdout, out.stderr + out.stdout


# -------------------------------------------------- roofline math
def test_roofline_memory_vs_compute_bound():
    # Intensity 10 FLOP/B, ridge at 100 -> memory bound, capped by BW.
    rl = xprof.roofline(1e12, 1e11, peak_flops=1e14,
                        peak_bytes_per_sec=1e12)
    assert rl["bound"] == "memory"
    assert rl["attainable_flops_per_sec"] == pytest.approx(1e13)
    assert rl["min_time_s"] == pytest.approx(0.1)
    # Intensity 1000 -> compute bound, capped by the FLOP roof.
    rl = xprof.roofline(1e14, 1e11, peak_flops=1e14,
                        peak_bytes_per_sec=1e12)
    assert rl["bound"] == "compute"
    assert rl["attainable_flops_per_sec"] == pytest.approx(1e14)


def test_roofline_ridge_point_and_degenerate_inputs():
    rl = xprof.roofline(1e12, 1e10, peak_flops=2e14,
                        peak_bytes_per_sec=1e12)
    assert rl["ridge_intensity"] == pytest.approx(200.0)
    zero = xprof.roofline(0.0, 0.0, 1e14, 1e12)
    assert zero["attainable_flops_per_sec"] == 0.0
    assert zero["min_time_s"] == 0.0


# -------------------------------------- replica-group parsing
def test_parse_replica_groups_explicit():
    assert xprof.parse_replica_groups("{{0,1},{2,3}}") == \
        [[0, 1], [2, 3]]
    assert xprof.parse_replica_groups("{{0,2},{1,3}}") == \
        [[0, 2], [1, 3]]
    assert xprof.parse_replica_groups("{}") == []


def test_parse_replica_groups_iota():
    # [2,2]<=[4]: ids 0..3 row-major, chunked into 2 groups of 2.
    assert xprof.parse_replica_groups("[2,2]<=[4]") == \
        [[0, 1], [2, 3]]
    # The transpose form walks iota([2,2]) by T(1,0): columns first.
    assert xprof.parse_replica_groups("[2,2]<=[2,2]T(1,0)") == \
        [[0, 2], [1, 3]]
    assert xprof.parse_replica_groups("[1,4]<=[4]") == [[0, 1, 2, 3]]


def test_parse_hlo_collectives_counts_definitions_not_references():
    hlo = """
      %all-reduce.17 = f32[8,4]{1,0} all-reduce(f32[8,4]{1,0} %p0), replica_groups={{0,1},{2,3}}, to_apply=%add
      %fusion.3 = f32[8,4]{1,0} fusion(f32[8,4]{1,0} %all-reduce.17), kind=kLoop
      %ag = bf16[16]{0} all-gather(bf16[8]{0} %p1), replica_groups=[2,2]<=[4], dimensions={0}
      %ars = (f32[4]{0}, f32[4]{0}) all-reduce-start(f32[4]{0} %p2), replica_groups={{0,1,2,3}}
      %ard = f32[4]{0} all-reduce-done((f32[4]{0}, f32[4]{0}) %ars)
    """
    colls = xprof.parse_hlo_collectives(hlo)
    ops = [c["op"] for c in colls]
    # The fusion consuming %all-reduce.17 is NOT a second all-reduce,
    # and the async -done half is skipped (-start already counted).
    assert ops == ["all-reduce", "all-gather", "all-reduce"]
    assert colls[0]["bytes"] == pytest.approx(8 * 4 * 4)
    assert colls[0]["groups"] == [[0, 1], [2, 3]]
    assert colls[1]["bytes"] == pytest.approx(16 * 2)  # bf16
    assert colls[1]["groups"] == [[0, 1], [2, 3]]
    # Tuple result type of the async start: both halves summed.
    assert colls[2]["bytes"] == pytest.approx(2 * 4 * 4)


_TPU_STEP_LINES = """
  %all-to-all.3 = bf16[1,2,8,1024,640]{3,4,1,2,0:T(8,128)(2,1)} all-to-all(bf16[1,2,8,1024,640]{3,4,1,2,0:T(8,128)(2,1)} %copy.1), channel_id=9, replica_groups={{0,1},{2,3}}, dimensions={1}
  %collective-permute-start.2 = (bf16[16,1024,1280]{2,1,0:T(8,128)(2,1)}, bf16[16,1024,1280]{2,1,0:T(8,128)(2,1)}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(bf16[16,1024,1280]{2,1,0:T(8,128)(2,1)} %slice.4), channel_id=7, source_target_pairs={{0,1},{1,0},{2,3},{3,2}}
  %collective-permute-done.2 = bf16[16,1024,1280]{2,1,0:T(8,128)(2,1)} collective-permute-done(%collective-permute-start.2)
  %collective-permute.5 = s32[16,1024,1]{2,1,0} collective-permute(s32[16,1024,1]{2,1,0} %p), source_target_pairs={{0,2},{2,0}}
  %all-reduce.17 = f32[8,4]{1,0} all-reduce(f32[8,4]{1,0} %p0), replica_groups={{0,1},{2,3}}, to_apply=%add
  %fusion.9 = bf16[16,1024,1280]{2,1,0} fusion(%collective-permute-done.2, %all-to-all.3), kind=kLoop
"""


@pytest.mark.parametrize("hlo,want", [
    (_TPU_STEP_LINES, {"all-reduce": 1, "all-gather": 0,
                       "reduce-scatter": 0, "all-to-all": 1,
                       "collective-permute": 2}),
    ("", dict.fromkeys(xprof.COLLECTIVE_OPS, 0)),
], ids=["resharded_activation", "none"])
def test_count_collectives_by_kind(hlo, want):
    """The per-compile counter of the step layer: every kind present, an
    async pair once, a consumer of a collective not at all; the permutes
    are counted and stay out of the per-axis wire bytes."""
    colls = xprof.parse_hlo_collectives(hlo)
    assert xprof.count_collectives(colls) == want
    axes = xprof.summarize_collectives(colls, {"fsdp": 2, "tensor": 2})
    assert sum(a["ops"] for a in axes.values()) == \
        want["all-reduce"] + want["all-to-all"]


# -------------------------------------- axis attribution
def test_attribute_axes_on_fsdp_tensor_mesh():
    sizes = {"fsdp": 2, "tensor": 2}
    # Flattened C-order: id = fsdp_coord * 2 + tensor_coord.
    assert xprof.attribute_axes([[0, 1], [2, 3]], sizes) == "tensor"
    assert xprof.attribute_axes([[0, 2], [1, 3]], sizes) == "fsdp"
    assert xprof.attribute_axes([[0, 1, 2, 3]], sizes) == \
        "fsdp+tensor"
    assert xprof.attribute_axes([[0], [1], [2], [3]], sizes) == "none"
    assert xprof.attribute_axes([[0, 9]], sizes) == "unknown"
    assert xprof.attribute_axes([[0, 1]], None) == "all"


def test_collective_wire_bytes_conventions():
    # all-reduce: 2B(g-1)/g; all-gather/all-to-all: B(g-1)/g of the
    # RESULT (gathered) size; reduce-scatter: B(g-1) of the shard.
    assert xprof.collective_wire_bytes("all-reduce", 100.0, 4) == \
        pytest.approx(150.0)
    assert xprof.collective_wire_bytes("all-gather", 100.0, 4) == \
        pytest.approx(75.0)
    assert xprof.collective_wire_bytes("reduce-scatter", 25.0, 4) == \
        pytest.approx(75.0)
    assert xprof.collective_wire_bytes("all-to-all", 100.0, 4) == \
        pytest.approx(75.0)
    assert xprof.collective_wire_bytes("all-reduce", 100.0, 1) == 0.0


def test_summarize_collectives_rolls_up_per_axis():
    sizes = {"fsdp": 2, "tensor": 2}
    colls = [
        {"op": "all-reduce", "bytes": 100.0,
         "groups": [[0, 1], [2, 3]]},           # tensor
        {"op": "all-gather", "bytes": 100.0,
         "groups": [[0, 2], [1, 3]]},           # fsdp
        {"op": "all-reduce", "bytes": 40.0, "groups": []},  # global
        {"op": "all-reduce", "bytes": 9.0,
         "groups": [[0], [1], [2], [3]]},       # none -> dropped
    ]
    out = xprof.summarize_collectives(colls, sizes)
    assert out["tensor"]["bytes"] == pytest.approx(100.0)  # 2B(g-1)/g
    assert out["tensor"]["by_op"]["all-reduce"] == \
        pytest.approx(100.0)
    assert out["fsdp"]["bytes"] == pytest.approx(50.0)
    # Empty replica_groups = one group of the whole world.
    assert out["fsdp+tensor"]["bytes"] == pytest.approx(60.0)
    assert "none" not in out
    assert sum(a["ops"] for a in out.values()) == 3


# -------------------------------------- report assembly + peaks
def test_build_report_decomposition_and_render():
    programs = {
        "train_step": {
            "flops": 1e12, "bytes": 2e10,
            "memory": {"argument": 1e9, "temp": 5e8, "peak": 1.5e9},
            "collectives": {
                "fsdp": {"bytes": 2e9, "by_op": {"all-gather": 2e9}},
                "tensor": {"bytes": 1e9,
                           "by_op": {"all-reduce": 1e9}}},
            "compiles": 1, "compile_seconds": 12.5}}
    rep = xprof.build_report(
        programs, {"train_step": {"step_time_s": 0.05}},
        peak_flops=100e12, peak_hbm=1e12, interconnect=100e9)
    row = rep["programs"]["train_step"]
    # intensity 50 < ridge 100 -> memory bound at 50 TFLOP/s.
    assert row["roofline"]["bound"] == "memory"
    assert row["roofline"]["attainable_flops_per_sec"] == \
        pytest.approx(50e12)
    assert row["achieved_flops_per_sec"] == pytest.approx(2e13)
    assert row["mfu"] == pytest.approx(0.2)
    assert row["of_attainable"] == pytest.approx(0.4)
    assert row["collectives"]["fsdp"]["byte_share"] == \
        pytest.approx(2 / 3)
    d = row["decomposition"]
    assert d["compute_min_s"] == pytest.approx(0.02)
    assert d["collective_min_s"] == pytest.approx(0.03)
    assert d["step_time_s"] == pytest.approx(0.05)
    assert d["shares"]["compute"] + d["shares"]["collective"] + \
        d["shares"]["other"] == pytest.approx(1.0)
    assert d["axis_time_shares"]["fsdp"] == pytest.approx(0.4)
    text = xprof.render_report(rep)
    for needle in ("train_step", "roofline", "axis fsdp",
                   "axis tensor", "decomposition", "compiles"):
        assert needle in text, text


def test_one_peak_table_keyed_by_device_kind():
    """train.config and xprof read ONE table (util/chips.py), keyed by
    what ``jax.devices()[0].device_kind`` reports; the v5e's spelling
    is "TPU v5 lite" (read off the chip)."""
    from ray_tpu.train.config import TelemetryConfig
    from ray_tpu.util import chips

    v5e = chips.peaks_for("TPU v5 lite")
    assert (v5e.gen, v5e.flops_per_sec, v5e.hbm_bytes_per_sec) == \
        ("v5e", 197e12, 819e9)
    assert xprof.resolve_peak_flops("TPU v5 lite") == 197e12
    assert xprof.resolve_peak_hbm("TPU v5 lite") == 819e9
    assert xprof.resolve_interconnect("TPU v5 lite") == 200e9
    # An explicit figure wins; the table is only the default.
    assert TelemetryConfig(
        peak_flops_per_device=5.0).resolved_peak_flops() == 5.0


def test_unknown_device_kind_is_an_error_not_v5e(monkeypatch):
    from ray_tpu.train.config import TelemetryConfig
    from ray_tpu.util import chips

    for var in ("RT_PEAK_FLOPS_PER_DEVICE", "RT_PEAK_HBM_BYTES_PER_SEC",
                "RT_INTERCONNECT_BYTES_PER_SEC"):
        monkeypatch.delenv(var, raising=False)
    with pytest.raises(chips.UnknownChipError, match="TPU v9 mega"):
        chips.peaks_for("TPU v9 mega")
    with pytest.raises(chips.UnknownChipError):
        xprof.resolve_peak_flops("cpu")
    # This process runs on the CPU backend (or none): no row, no MFU.
    with pytest.raises(chips.UnknownChipError):
        TelemetryConfig().resolved_peak_flops()
    with pytest.raises(chips.UnknownChipError):
        xprof.build_report({"p": {"flops": 1.0, "bytes": 1.0}})


def test_peak_resolution_env_overrides(monkeypatch):
    monkeypatch.setenv("RT_PEAK_FLOPS_PER_DEVICE", "123e12")
    monkeypatch.setenv("RT_PEAK_HBM_BYTES_PER_SEC", "456e9")
    monkeypatch.setenv("RT_INTERCONNECT_BYTES_PER_SEC", "7e9")
    assert xprof.resolve_peak_flops() == pytest.approx(123e12)
    assert xprof.resolve_peak_hbm() == pytest.approx(456e9)
    assert xprof.resolve_interconnect() == pytest.approx(7e9)
    monkeypatch.delenv("RT_PEAK_FLOPS_PER_DEVICE")
    assert xprof.resolve_peak_flops("TPU v5") == pytest.approx(
        xprof.chips.CHIP_PEAKS["TPU v5"].flops_per_sec)


# -------------------------------------- telemetry aggregation
def _gauge_snap(name, series):
    return {"name": name, "type": "gauge",
            "series": [{"tags": t, "value": v} for t, v in series]}


def test_cluster_summary_aggregates_xla_section(monkeypatch):
    from ray_tpu.util import state as state_api
    from ray_tpu.util import telemetry

    sources = {
        "worker-1": [
            _gauge_snap("rt_xla_cost_flops",
                        [({"fn": "train_step",
                           "device_kind": "TPU v5 lite"}, 1e12)]),
            _gauge_snap("rt_xla_cost_bytes",
                        [({"fn": "train_step"}, 2e10)]),
            _gauge_snap("rt_xla_memory_bytes",
                        [({"fn": "train_step", "kind": "peak"},
                          1.5e9)]),
            _gauge_snap("rt_xla_collective_bytes",
                        [({"fn": "train_step", "axis": "fsdp",
                           "op": "all-gather"}, 2e9),
                         ({"fn": "train_step", "axis": "tensor",
                           "op": "all-reduce"}, 1e9)]),
            _gauge_snap("rt_xla_compiles_total",
                        [({"fn": "train_step"}, 1.0)]),
            _gauge_snap("rt_xla_compile_seconds_total",
                        [({"fn": "train_step"}, 9.0)]),
            _gauge_snap("rt_xla_device_memory_bytes",
                        [({"device": "0", "kind": "used"}, 8e9),
                         ({"device": "0", "kind": "limit"}, 16e9)]),
        ],
        "worker-2": [
            # Identical static facts (max-merge), own compile count.
            _gauge_snap("rt_xla_cost_flops",
                        [({"fn": "train_step"}, 1e12)]),
            _gauge_snap("rt_xla_compiles_total",
                        [({"fn": "train_step"}, 2.0)]),
            _gauge_snap("rt_xla_compile_seconds_total",
                        [({"fn": "train_step"}, 11.0)]),
        ],
    }
    monkeypatch.setattr(state_api, "telemetry",
                        lambda address=None: {"sources": sources})
    monkeypatch.setattr(state_api, "metrics_history",
                        lambda address=None: {})
    summary = telemetry.cluster_summary()
    prog = summary["xla"]["programs"]["train_step"]
    assert prog["flops"] == pytest.approx(1e12)       # max, not sum
    assert prog["compiles"] == pytest.approx(3.0)     # summed
    assert prog["compile_seconds"] == pytest.approx(20.0)
    assert prog["collectives"]["fsdp"]["bytes"] == pytest.approx(2e9)
    assert prog["collectives"]["tensor"]["bytes"] == \
        pytest.approx(1e9)
    dm = summary["xla"]["device_memory"]["worker-1"]["0"]
    assert dm["used"] == pytest.approx(8e9)
    assert dm["limit"] == pytest.approx(16e9)
    text = telemetry.render_text(summary)
    assert "XLA compiles" in text and "3 (20.00s total" in text
    assert "Device memory" in text

    # cluster_report over the same summary: roofline + axis shares
    # come out the other end (the `rt perf` path minus the fetch).
    rep = xprof.cluster_report(summary=summary)
    row = rep["programs"]["train_step"]
    assert row["roofline"]["flops"] == pytest.approx(1e12)
    assert row["collectives"]["fsdp"]["byte_share"] == \
        pytest.approx(2 / 3)
    assert rep["device_memory"]["worker-1"]["0"]["used"] == \
        pytest.approx(8e9)
    assert "train_step" in xprof.render_report(rep)


# -------------------------------------- doctor finders
def test_doctor_flags_recompile_churn():
    from ray_tpu.util import doctor

    sources = {"w1": [_gauge_snap(
        "rt_xla_compiles_total",
        [({"fn": "llm_prefill[128]"}, 40.0),
         ({"fn": "train_step"}, 1.0)])]}
    finds = doctor.find_recompile_churn(sources, min_compiles=8.0)
    assert len(finds) == 1
    f = finds[0]
    assert f["check"] == "recompile_churn"
    assert f["severity"] == "warning"
    assert "llm_prefill[128]" in f["summary"]
    assert doctor.find_recompile_churn(sources,
                                       min_compiles=50.0) == []


def test_doctor_flags_device_memory_pressure():
    from ray_tpu.util import doctor

    def snap(used, peak, limit):
        return [_gauge_snap(
            "rt_xla_device_memory_bytes",
            [({"device": "0", "kind": "used"}, used),
             ({"device": "0", "kind": "peak"}, peak),
             ({"device": "0", "kind": "limit"}, limit)])]

    # 95% used -> warning; 99% -> critical; 50% -> quiet; peak
    # brushing the ceiling warns even when current use is low.
    assert doctor.find_device_memory_pressure(
        {"w": snap(15.2e9, 15.3e9, 16e9)})[0]["severity"] == "warning"
    assert doctor.find_device_memory_pressure(
        {"w": snap(15.9e9, 15.9e9, 16e9)})[0]["severity"] == \
        "critical"
    assert doctor.find_device_memory_pressure(
        {"w": snap(8e9, 9e9, 16e9)}) == []
    assert doctor.find_device_memory_pressure(
        {"w": snap(8e9, 15.9e9, 16e9)})[0]["severity"] == "warning"
    # No limit reported (CPU backend) -> no finding, no div-by-zero.
    assert doctor.find_device_memory_pressure(
        {"w": snap(8e9, 9e9, 0.0)}) == []


def test_diagnose_accepts_metric_sources():
    from ray_tpu.util import doctor

    sources = {"w1": [_gauge_snap("rt_xla_compiles_total",
                                  [({"fn": "train_step"}, 30.0)])]}
    rep = doctor.diagnose(feed={}, tasks=[], spans=[], load={},
                          pgs=[], nodes=[], ledgers=[],
                          metric_sources=sources)
    assert any(f["check"] == "recompile_churn"
               for f in rep["findings"])


# ------------------------- live harvest: both mesh axes (4 devices)
def test_sharded_step_registers_collectives_on_both_axes():
    """A real sharded GPT-2 train step on a 2x2 fsdp x tensor mesh
    (4 virtual CPU devices, one process): the telemetry path AOT-
    compiles, the xprof plane harvests the post-SPMD HLO, and the
    collective wire bytes land nonzero on BOTH mesh axes."""
    script = textwrap.dedent(f"""
        import json
        import sys
        sys.path.insert(0, {REPO!r})
        import jax
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec

        from ray_tpu.models.gpt2 import (GPT2Config, gpt2_init,
                                         gpt2_loss_fn)
        from ray_tpu.parallel.mesh import gang_mesh
        from ray_tpu.parallel.partition_rules import tree_shardings
        from ray_tpu.train import distributed as dist
        from ray_tpu.train.train_step import (
            TrainState, make_optimizer, make_sharded_train_step)
        from ray_tpu.util import xprof
        from ray_tpu.util.metrics import registry

        cfg = GPT2Config(vocab_size=256, n_layer=1, n_head=4,
                         d_model=64, d_ff=128, max_seq=32)
        params = gpt2_init(cfg, jax.random.PRNGKey(0))
        optimizer = make_optimizer(total_steps=10)
        state = TrainState.create(params, optimizer)
        mesh = gang_mesh({{"fsdp": 2, "tensor": 2}})
        assert dist.mesh_axis_sizes(mesh) == {{"fsdp": 2,
                                               "tensor": 2}}
        state, specs = dist.shard_train_state(
            state, mesh, dist.rules_for_model("gpt2"))
        shardings = tree_shardings(mesh, specs)
        step = make_sharded_train_step(
            lambda p, b: gpt2_loss_fn(cfg, p, b, loss_chunk=0),
            optimizer, mesh=mesh, state_shardings=shardings,
            batch_sharding=NamedSharding(mesh,
                                         PartitionSpec("fsdp")),
            telemetry=True)
        tokens = np.random.default_rng(0).integers(
            0, cfg.vocab_size, (4, cfg.max_seq + 1)).astype("int32")
        batch = {{"tokens": jax.device_put(
            tokens, NamedSharding(mesh, PartitionSpec("fsdp")))}}
        for _ in range(2):
            state, metrics = step(state, batch)
        _ = float(jax.device_get(metrics["loss"]))

        prog = xprof.local_programs().get("train_step")
        assert prog, "train_step never registered with xprof"
        colls = prog["collectives"]
        fsdp_b = sum(a["bytes"] for ax, a in colls.items()
                     if "fsdp" in ax)
        tensor_b = sum(a["bytes"] for ax, a in colls.items()
                       if "tensor" in ax)
        assert fsdp_b > 0, f"no fsdp-axis bytes: {{colls}}"
        assert tensor_b > 0, f"no tensor-axis bytes: {{colls}}"
        assert prog["flops"] > 0
        # The executable's collectives by kind, once per compile, where
        # the step keeps what it knows of the program that ran.
        counts = prog["collective_counts"]
        assert set(counts) == set(xprof.COLLECTIVE_OPS), counts
        assert counts["all-reduce"] > 0 and counts["all-gather"] > 0
        assert step.collective_counts == counts
        # ... and what the traced loss said of itself (whole logits
        # here: loss_chunk=0).
        assert prog["loss"] == step.notes["loss"] == {{
            "path": "whole", "token_shards": 1, "chunk": 0}}

        # ...and the facts went out as rt_xla_* gauges.
        names = {{s["name"] for s in registry().snapshot()}}
        for need in ("rt_xla_cost_flops", "rt_xla_collective_bytes",
                     "rt_xla_collective_ops", "rt_xla_compiles_total"):
            assert need in names, names
        print("AXES_OK", json.dumps(
            {{"fsdp": fsdp_b, "tensor": tensor_b}}))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=420,
                         env=env)
    assert "AXES_OK" in out.stdout, out.stderr[-4000:] + out.stdout
