"""What PR 26 added to the benchmark, by hand on the CPU: the loader finds
benchmark/families/olmoe.py by name; the reference against the program at
the tiny preset through the family row; serve-olmoe-1b-7b-sat rehearsed at
a tiny size through rehearse_run.py (traced and not); the four moe.*
readers on a hand-made capture; the roofline functions on worked
numbers."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import rehearsal
from benchmark.harness import manifest, moe_flops, moe_phases
from benchmark.harness.families import family_of

TINY = {
    "family": "olmoe", "source": "a tiny preset for CPU rehearsals",
    "hidden_size": 64, "intermediate_size": 32,
    "max_position_embeddings": 128, "norm_topk_prob": False,
    "num_attention_heads": 4, "num_experts": 8, "num_experts_per_tok": 2,
    "num_hidden_layers": 2, "num_key_value_heads": 4, "rms_norm_eps": 1e-05,
    "rope_theta": 10000, "vocab_size": 512, "router_aux_loss_coef": 0.01,
    "router_z_loss_coef": 0.001, "compute_dtype": "bfloat16",
    "param_dtype": "bfloat16", "reduced": []}


def test_loader_finds_the_family_file_by_name():
    fam = family_of(TINY)
    assert (fam.name, fam.engine_model, fam.reference) == (
        "olmoe", "olmoe", "olmoe_ref")
    cfg = fam.program_config(TINY, attn_impl="dense", remat=False)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.qk_norm) == (
        8, 2, True)
    assert cfg.param_dtype == jnp.bfloat16 and not cfg.remat
    assert fam.sizes(TINY)["vocab"] == 512
    with pytest.raises(LookupError, match="benchmark/families/nope.py"):
        family_of({"family": "nope"})


def test_published_config_holds_every_catalog_number():
    cell = manifest.load_cell("serve-olmoe-1b-7b-sat")
    catalog = {"attention_bias": False, "clip_qkv": None,
               "hidden_act": "silu", "hidden_size": 2048,
               "intermediate_size": 1024, "max_position_embeddings": 4096,
               "model_type": "olmoe", "norm_topk_prob": False,
               "num_attention_heads": 16, "num_experts": 64,
               "num_experts_per_tok": 8, "num_hidden_layers": 16,
               "num_key_value_heads": 16, "rms_norm_eps": 1e-05,
               "rope_scaling": None, "rope_theta": 10000,
               "tie_word_embeddings": False, "vocab_size": 50304}
    differ = {k for k, v in catalog.items() if cell.config.get(k, "-") != v}
    assert differ == set(cell.config["reduced"]) == {"num_hidden_layers"}
    assert cell.traffic["pool"] == 192
    base = json.load(open(os.path.join(
        manifest.BENCH_DIR, "traffic", "offline-closed.json")))
    assert {k for k in base if base[k] != cell.traffic[k]} == {
        "pool", "why_pool"}


def test_reference_against_program_through_the_family_row():
    """bf16 weights, float32 compute on both sides: the same equations."""
    from benchmark.reference import olmoe_ref

    config = dict(TINY, compute_dtype="float32")
    fam = family_of(config)
    cfg = fam.program_config(config, attn_impl="dense", remat=False)
    params = fam.init(cfg, jax.random.PRNGKey(3))
    assert all(x.dtype == jnp.bfloat16
               for x in jax.tree_util.tree_leaves(params))
    params = jax.tree_util.tree_map(
        lambda w: w if w.ndim == 1 else 8 * w, params)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, 512, (2, 33)), jnp.int32)
    from ray_tpu.models.llama import Llama

    ours = Llama(cfg).apply(params, tokens[:, :-1])
    ref = olmoe_ref.forward(config, params, tokens[:, :-1])
    assert float(jnp.max(jnp.abs(ours - ref))) < 1e-4
    loss = fam.loss(cfg, params, {"tokens": tokens})
    assert abs(float(loss) - float(olmoe_ref.loss(config, params, tokens))
               ) < 1e-4


# ------------------------------------------------------------- rehearsal

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """rehearsal.build's copy, with a tiny OLMoE cell added beside its
    tiny GPT-2 ones: files and entries only."""
    root = rehearsal.build(str(tmp_path_factory.mktemp("bench")))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    m["configs"].append({"name": "olmoe-tiny", "source": TINY["source"],
                         "file": "benchmark/configs/olmoe-tiny.json",
                         "reduced": [], "why": "CPU rehearsal"})
    m["workloads"].append({"name": "tiny-olmoe-sat", "config": "olmoe-tiny",
                           "traffic": "tiny-closed", "chips": 1,
                           "why": "CPU rehearsal"})
    for group in ("end_to_end", "per_layer"):
        for metric in m[group]:
            if "serve-olmoe-1b-7b-sat" in metric.get("workloads", ()):
                metric["workloads"].append("tiny-olmoe-sat")
    rehearsal._write(path, m)
    rehearsal._write(os.path.join(root, "benchmark/configs/olmoe-tiny.json"),
                     TINY)
    rehearsal._write(
        os.path.join(root, "benchmark/cells/tiny-olmoe-sat.json"),
        {"engine": {"page_size": 4, "num_pages": 128, "max_batch": 4,
                    "max_context": 64}})
    return root


@pytest.mark.parametrize("trace", [0, 1])
def test_serving_cell_rehearsed_on_cpu(root, trace):
    out = rehearsal.run_cell(root, "tiny-olmoe-sat", trace=trace)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    line, info = json.loads(lines[-1]), json.loads(lines[-2])["info"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    if not trace:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
        return
    # the counter's reader needs no device plane; the three that read
    # moe.* scopes off a TPU's trace return nothing here
    hit = line["metrics"]["moe.experts_hit.sat"]["value"]
    per_run = info["detail"]["phases"]["moe_routing_per_run"]
    assert 2 <= hit <= 8 and per_run["layers"] == 2
    assert per_run["pairs"] <= 4 * 2 * 2        # rows x k x layers
    assert "engine.step_ms.sat" in line["metrics"]


# ----------------------------------------------- readers, worked numbers

def test_roofline_functions_on_worked_numbers():
    """One decode run of the cell: 16 rows x 8 = 128 pairs a layer, 56
    experts hit a layer, 8 layers."""
    d, f = 2048, 1024
    assert moe_flops.experts_flops(128 * 8, d, f) == 128 * 8 * 6 * d * f
    nbytes = moe_flops.experts_bytes(56 * 8, 128 * 8, d, f)
    assert nbytes == (56 * 8 * 3 * d * f + 128 * 8 * 2 * d) * 2
    # 5.64 GB of matrices: 6.9 ms at 819 GB/s; 12.9 GFLOP: 0.07 ms
    assert abs(nbytes / 819e9 - 6.89e-3) < 0.05e-3


def _ctx(tmp_path, scopes, kernels=()):
    """A capture made by hand: two decode runs and one prefill run of
    jit_fwd, operations under the given scopes."""
    from xplane_stats import encode

    ms = 1_000_000
    ops, meta = [], {}
    for run_start in (10 * ms, 40 * ms, 70 * ms):
        for j, (scope, dur) in enumerate(scopes):
            name = f"%op.{j} = bf16[8] fusion(%x), kind=kLoop"
            ops.append((name, run_start + 2 * j * ms, dur * ms))
            meta[name] = {
                "tf_op": f"jit(fwd)/Llama/layer_0/mlp/moe/{scope}/dot"}
        # the compiler's grouped-matmul kernels carry no scope path
        for j, (kernel, dur) in enumerate(kernels, len(scopes)):
            ops.append((f"%{kernel}.{j} = bf16[8] custom-call(%x), "
                        'custom_call_target="tpu_custom_call"',
                        run_start + 2 * j * ms, dur * ms))
    path = str(tmp_path / "t.xplane.pb")
    with open(path, "wb") as f:
        f.write(encode([
            ("/device:TPU:0", {
                "XLA Modules": [("jit_fwd(1)", 10 * ms, 25 * ms),
                                ("jit_fwd(1)", 40 * ms, 25 * ms),
                                ("jit_fwd(2)", 70 * ms, 25 * ms)],
                "XLA Ops": ops}),
            ("/host:CPU", {"engine": [
                ("llm.decode", 9 * ms, 28 * ms),
                ("llm.decode", 39 * ms, 28 * ms),
                ("llm.prefill", 69 * ms, 28 * ms, {"bucket": 32}),
                ("llm.step", 9 * ms, 29 * ms),
                ("llm.step", 39 * ms, 29 * ms)]})], meta))
    moe = {"layer_runs": 0, "pairs": 0, "experts_hit": 0, "max_load": 0}
    after = {"layer_runs": 80, "pairs": 10 * 1024, "experts_hit": 10 * 448,
             "max_load": 10 * 40}
    from benchmark.harness import peaks

    return {"trace_path": path, "sizes": {"n_layer": 8, "d_model": 2048,
                                          "d_ff": 1024},
            "peaks": peaks.peaks_for("TPU v5 lite"),
            "serve": {"before": {"moe": moe}, "at_end": {"moe": after}}}


def test_readers_on_a_hand_made_capture(tmp_path):
    ctx = _ctx(tmp_path, [("moe.route", 1), ("moe.dispatch", 0.5),
                          ("moe.experts", 1), ("moe.combine", 2)],
               kernels=[("ragged-dot-metadata", 0.5),
                        ("ragged-dot-none", 4), ("ragged-dot-none", 5)])
    read = {name: manifest.load_reader(name) for name in (
        "moe.experts_ms.sat", "moe.route_ms.sat", "moe.experts_hit.sat",
        "moe.experts_roofline.sat")}
    # the prefill run's operations are not a decode run's
    assert read["moe.experts_ms.sat"](ctx) == pytest.approx(10.0)
    assert read["moe.route_ms.sat"](ctx) == pytest.approx(4.0)
    assert read["moe.experts_hit.sat"](ctx) == pytest.approx(56.0)
    # 448 experts hit: 5.64 GB = 6.89 ms at the peak, of 10 ms
    assert read["moe.experts_roofline.sat"](ctx) == pytest.approx(
        68.9, abs=0.2)
    assert ctx["info"]["phases"]["moe_experts_roofline"]["bound"] == "memory"


def test_readers_return_nothing_without_names_or_counters(tmp_path):
    ctx = _ctx(tmp_path, [("mlp_in", 5)])
    ctx["serve"] = {"before": {}, "at_end": {}}
    for name in ("moe.experts_ms.sat", "moe.route_ms.sat",
                 "moe.experts_hit.sat", "moe.experts_roofline.sat"):
        assert manifest.load_reader(name)(ctx) is None
