"""The xing4_0 family row and its cell: the loader finds
benchmark/families/xing4_0.py by name and it maps the configuration to the
program's config (refusing what models/xing.py does not write down);
serve-xing4.0-29b-a4b-4k rehearsed at a tiny size through rehearse_run.py
(traced and not); the residual path's operations and bytes on worked
numbers, and its readers on a capture made by hand."""

import json
import os

import pytest

import rehearsal
from benchmark.harness import hc_flops, manifest
from benchmark.harness.families import family_of
from test_kimi_k2 import TINY as KIMI_TINY
from test_kimi_k2 import _ctx

CELL = "serve-xing4.0-29b-a4b-4k"
TINY = {**{k: v for k, v in KIMI_TINY.items()
           if k not in ("first_routed_expert", "published")},
        "family": "xing4_0", "model_type": "xing4_0",
        "num_hidden_layers": 4, "first_k_dense_replace": 2,
        "n_routed_experts": 8, "routed_scaling_factor": 2,
        "rms_norm_eps": 1e-06, "hc_mult": 4, "hc_sinkhorn_iters": 20,
        "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
        "mhc_h_res_clamp_max": 30,
        "hc_init": {"map_gate": [0.4, 0.4, 0.125],
                    "map_bias_std": [0.5, 0.5, 0.3],
                    "b_res_diagonal": 1.0, "phi_std": 0.02}}


def test_loader_finds_the_family_file_by_name():
    fam = family_of(TINY)
    assert (fam.name, fam.engine_model, fam.reference) == (
        "xing4_0", "xing40", "xing4_0_ref")
    cfg = fam.program_config(TINY, attn_impl="dense", remat=False)
    assert (cfg.n_experts, cfg.held_experts, cfg.experts_per_token,
            cfg.n_dense_layers, cfg.n_layer) == (8, None, 2, 2, 4)
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps,
            cfg.hc_clamp_min, cfg.hc_clamp_max) == (4, 20, 1e-6, -30, 30)
    sizes = fam.sizes(TINY)
    assert (sizes["n_layer"], sizes["kv_layers"], sizes["hc_mult"],
            sizes["hc_sublayers"]) == (2, 4, 4, 8)
    for key, other in (("num_nextn_predict_layers", 1), ("n_group", 2),
                       ("topk_group", 2), ("scoring_func", "softmax"),
                       ("topk_method", "greedy")):
        with pytest.raises(ValueError, match=key):
            fam.program_config(dict(TINY, **{key: other}))
    published = manifest.load_cell(CELL).config
    cfg = fam.program_config(published, attn_impl="dense", remat=False)
    assert (cfg.n_layer, cfg.d_model, cfg.n_experts, cfg.held_experts,
            cfg.vocab_size, cfg.hc_mult) == (7, 3584, 64, None, 131072, 4)


def test_the_cells_traffic_is_the_kimi_cells_letter_for_letter():
    mine = manifest.load_cell(CELL).traffic
    theirs = manifest.load_cell("serve-kimi-k2.5-4k").traffic
    for key in theirs:
        if key not in ("check", "why_pool"):
            assert mine[key] == theirs[key], key


# ------------------------------------------------------------- rehearsal

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = rehearsal.build(str(tmp_path_factory.mktemp("bench")))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    m["configs"].append({"name": "xing-tiny", "source": TINY["source"],
                         "file": "benchmark/configs/xing-tiny.json",
                         "reduced": [], "why": "CPU rehearsal"})
    m["workloads"].append({"name": "tiny-xing-sat", "config": "xing-tiny",
                           "traffic": "tiny-closed", "chips": 1,
                           "why": "CPU rehearsal"})
    for group in ("end_to_end", "per_layer"):
        for metric in m[group]:
            if CELL in metric.get("workloads", ()):
                metric["workloads"].append("tiny-xing-sat")
    rehearsal._write(path, m)
    rehearsal._write(
        os.path.join(root, "benchmark/configs/xing-tiny.json"), TINY)
    rehearsal._write(
        os.path.join(root, "benchmark/cells/tiny-xing-sat.json"),
        {"engine": {"page_size": 4, "num_pages": 128, "max_batch": 4,
                    "max_context": 64}})
    return root


@pytest.mark.parametrize("trace", [0, 1])
def test_serving_cell_rehearsed_on_cpu(root, trace):
    out = rehearsal.run_cell(root, "tiny-xing-sat", trace=trace)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0
    if not trace:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
        return
    # the counters' readers need no device plane; those that read scopes
    # off a TPU's trace return nothing here
    assert 0 < line["metrics"]["moe.experts_hit.sat"]["value"] <= 8
    assert line["metrics"]["moe.compact_share.sat"]["value"] == 0
    assert "engine.step_ms.sat" in line["metrics"]
    assert "hc.map_ms.sat" not in line["metrics"]


# ----------------------------------------------- readers, worked numbers

def test_residual_path_flops_and_bytes_on_worked_numbers():
    """A position a sublayer at n = 4, d = 3584: (4 x 4 + 2) x 3584 = 64,512
    numbers = 129,024 bytes in bf16; FLOPs 3 x 14336 + 2 x 14336 x 24 +
    2 x 14336 + 2 x 16 x 3584 + 2 x 14336 + 4 x 16 x 20 = 904,448.  Phi a
    sublayer: 14336 x 24 x 4 = 1,376,256 bytes.  A prefill of the 4,096
    bucket through 14 sublayers: 7.418 GB (9.06 ms at 819 GB/s) and 51.9
    GFLOP (0.26 ms at the peak): the bytes bound it."""
    assert hc_flops.hc_bytes(1, 1, 4, 3584) == 129_024 + 1_376_256
    assert hc_flops.hc_flops(1, 1, 4, 3584, 20) == 904_448
    b = hc_flops.hc_bytes(4096, 14, 4, 3584)
    f = hc_flops.hc_flops(4096, 14, 4, 3584, 20)
    assert b == 14 * (4096 * 129_024 + 1_376_256)
    assert b == pytest.approx(7.418e9, rel=1e-3)
    assert f == pytest.approx(51.86e9, rel=1e-3)
    assert b / 819e9 > 30 * f / 197e12


def test_readers_on_a_hand_made_capture(tmp_path):
    ctx = _ctx(tmp_path,
               [("attn_hc/hc.map/norm/mul", 0.2),
                ("attn_hc/hc.map/dot_general", 0.1),
                ("attn_hc/hc.pre/mul", 0.05),
                ("attn/mla.q/wq_a/dot_general", 0.3),
                ("hc.post/add", 0.15),
                ("mlp/hc.post/add", 0.15),
                ("hc.end/reduce_sum", 0.01)],
               [("mlp_hc/hc.map/dot_general", 0.5),
                ("mlp_hc/hc.pre/mul", 0.25),
                ("mlp/hc.post/add", 0.25),
                ("hc.begin/broadcast", 0.02),
                ("mlp/moe/moe.experts/ragged_dot", 10)])
    ctx["sizes"].update(hc_mult=4, hc_sublayers=14, d_model=3584)
    ctx["cell"] = manifest.load_cell(CELL)
    read = {name: manifest.load_reader(name) for name in (
        "hc.map_ms.sat", "hc.mix_ms.sat", "hc.prefill_ms.sat",
        "hc.prefill_roofline.sat")}
    assert read["hc.map_ms.sat"](ctx) == pytest.approx(0.3)
    assert read["hc.mix_ms.sat"](ctx) == pytest.approx(0.35)
    assert read["hc.prefill_ms.sat"](ctx) == pytest.approx(1.0)
    least = hc_flops.hc_bytes(256, 14, 4, 3584) / 819e9
    assert read["hc.prefill_roofline.sat"](ctx) == pytest.approx(
        100 * least / 1e-3, rel=1e-6)
    cap = ctx["info"]["phases"]["hc_capture"]
    assert (cap["decode_runs"], cap["prefill_runs"]) == (2, 1)
    assert cap["decode_ms_by_scope"]["hc.end"] == pytest.approx(0.01)
    assert cap["prefill_ms_by_bucket"] == {"256": [pytest.approx(1.0)]}
    # a program without the names: nothing to read, no raise
    none = _ctx(tmp_path, [("attn/mla.q/wq_a/dot_general", 0.3)],
                [("attn/mla.q/wq_a/dot_general", 1)])
    none["cell"] = ctx["cell"]
    assert all(r(none) is None for r in read.values())
