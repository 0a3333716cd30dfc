"""What PR 38 added to the benchmark, by hand on the CPU: the loader finds
benchmark/families/kimi_k2.py by name and it maps the configuration to the
program's config (the held share, the router's width, YaRN); the
configuration file holds every number of the catalog's row; the reference
against the program through the family row; serve-kimi-k2.5-4k rehearsed
at a tiny size through rehearse_run.py (traced and not); latent
attention's FLOPs and bytes on worked numbers; the four new readers on a
hand-made capture."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import rehearsal
from benchmark.harness import manifest, mla_flops
from benchmark.harness.families import family_of

CELL = "serve-kimi-k2.5-4k"
TINY = {
    "family": "kimi_k2", "source": "a tiny preset for CPU rehearsals",
    "attention_bias": False, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 96,
    "kv_lora_rank": 24, "max_position_embeddings": 128,
    "model_type": "kimi_k2", "moe_intermediate_size": 32,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 4,
    "first_routed_expert": 2, "published": {"n_routed_experts": 8},
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts_per_tok": 2,
    "num_hidden_layers": 3, "num_key_value_heads": 4,
    "num_nextn_predict_layers": 0, "q_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "rms_norm_eps": 1e-05,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 4,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 32,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.827,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 16,
    "vocab_size": 512, "route_norm_eps": 1e-20, "expert_bias_std": 0.005,
    "compute_dtype": "bfloat16", "param_dtype": "bfloat16", "reduced": []}


def test_loader_finds_the_family_file_by_name():
    fam = family_of(TINY)
    assert (fam.name, fam.engine_model, fam.reference) == (
        "kimi_k2", "kimik2", "kimi_k2_ref")
    cfg = fam.program_config(TINY, attn_impl="dense", remat=False)
    assert (cfg.n_experts, cfg.first_expert, cfg.held_experts,
            cfg.experts_per_token, cfg.n_dense_layers, cfg.d_ff,
            cfg.moe_d_ff, cfg.n_shared_experts) == (8, 2, 4, 2, 1, 96, 32, 1)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim) == (32, 24, 16, 8, 16)
    assert cfg.yarn == (4.0, 32, 32.0, 1.0)
    assert cfg.routed_scaling_factor == 2.827
    assert cfg.param_dtype == jnp.bfloat16 and not cfg.remat
    # what moe_phases.py divides layer_runs by is the layers WITH experts,
    # and its bytes are of the experts HELD
    sizes = fam.sizes(TINY)
    assert (sizes["n_layer"], sizes["kv_layers"], sizes["d_ff"],
            sizes["n_experts"], sizes["router_experts"], sizes["vocab"],
            sizes["kv_lora_rank"], sizes["qk_rope_head_dim"],
            sizes["qk_nope_head_dim"], sizes["v_head_dim"],
            sizes["head_dim"]) == (2, 3, 32, 4, 8, 512, 24, 8, 16, 16, 24)
    for other in ({"n_group": 8}, {"scoring_func": "softmax"},
                  {"tie_word_embeddings": True}, {"route_norm_eps": 0.0}):
        with pytest.raises(ValueError, match="source's choices"):
            fam.program_config(dict(TINY, **other))


def test_published_config_holds_every_catalog_number():
    cell = manifest.load_cell(CELL)
    catalog = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "hidden_act": "silu", "hidden_size": 7168,
        "intermediate_size": 18432, "kv_lora_rank": 512,
        "max_position_embeddings": 262144, "model_type": "kimi_k2",
        "moe_intermediate_size": 2048, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 384, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 64,
        "num_experts_per_tok": 8, "num_hidden_layers": 61,
        "num_key_value_heads": 64, "num_nextn_predict_layers": 0,
        "q_lora_rank": 1536, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "rope_theta": 50000, "routed_scaling_factor": 2.827,
        "scoring_func": "sigmoid", "seq_aux": True, "tf_legacy_loss": False,
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 163840}
    differ = {k for k, v in catalog.items() if cell.config.get(k, "-") != v}
    assert differ == set(cell.config["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert all(cell.config["published"][k] == catalog[k] for k in differ)
    assert (cell.config["num_hidden_layers"], cell.config["n_routed_experts"],
            cell.config["vocab_size"]) == (7, 12, 163840 // 8)
    assert set(cell.config["reduced_why"]) == set(cell.config["reduced"])
    assert {"rope_pairing", "initializer_range", "expert_bias",
            "route_norm_eps", "grouping", "compute_dtype",
            "latent_row"} <= set(cell.config["assumed"])
    fam = family_of(cell.config)
    cfg = fam.program_config(cell.config)
    assert (cfg.n_layer, cfg.n_moe_layers, cfg.n_experts, cfg.first_expert,
            cfg.held_experts, cfg.vocab_size) == (7, 6, 384, 0, 12, 20480)
    sizes = fam.sizes(cell.config)
    assert (sizes["n_layer"], sizes["n_experts"], sizes["d_ff"]) == (
        6, 12, 2048)
    # the traffic: offline-closed-768's file with the issue's parameters
    t = cell.traffic
    assert (t["clients"], t["pool"], t["order_block"], t["order_seed"],
            t["fill_limit_s"], t["max_total"], t["shared_prefix_tokens"]
            ) == (24, 768, 24, 23, 60, 4096, 0)
    assert t["prompt_len"] == {"dist": "lognormal", "median": 2048,
                               "sigma": 0.7, "min": 256, "max": 3840}
    assert t["output_len"] == {"dist": "lognormal", "median": 128,
                               "sigma": 0.6, "min": 16, "max": 256}
    base = json.load(open(os.path.join(
        manifest.BENCH_DIR, "traffic", "offline-closed-768.json")))
    assert t["sampling"] == base["sampling"]
    assert t["check"]["greedy_sample"] == 2
    assert t["check"]["max_positions"] >= 768
    assert cell.settings["engine"] == {
        "page_size": 16, "num_pages": 4096, "max_batch": 16,
        "prefill_token_budget": 4112, "max_context": 4096}
    assert cell.chips == 1


def test_reference_against_program_through_the_family_row():
    """bf16 weights, float32 compute on both sides: the same equations,
    with the share of the experts (4 of 8, from the third) on both."""
    from benchmark.reference import kimi_k2_ref as ref
    from ray_tpu.models.kimi import KimiK2

    config = dict(TINY, compute_dtype="float32")
    fam = family_of(config)
    cfg = fam.program_config(config, attn_impl="dense", remat=False)
    params = fam.init(cfg, jax.random.PRNGKey(3))
    leaves = jax.tree_util.tree_leaves_with_path(params)
    assert all(x.dtype == (jnp.float32 if path[-1].key == "expert_bias"
                           else jnp.bfloat16) for path, x in leaves)
    moe = params["params"]["layer_2"]["moe"]
    assert moe["w_up"].shape == (4, 64, 32)         # the held ones
    assert moe["router"].shape == (64, 8)           # all of them
    assert float(jnp.std(moe["expert_bias"])) > 0.001   # not zero
    assert "moe" not in params["params"]["layer_0"]     # the dense layer
    params = jax.tree_util.tree_map(
        lambda w: w if w.ndim == 1 else 8 * w, params)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, 512, (2, 41)), jnp.int32)
    ours = KimiK2(cfg).apply(params, tokens[:, :-1])
    want = ref.forward(config, params, tokens[:, :-1])
    assert float(jnp.std(want)) > 0.05
    assert float(jnp.max(jnp.abs(ours - want))) < 2e-4
    loss = fam.loss(cfg, params, {"tokens": tokens})
    assert abs(float(loss) - float(ref.loss(config, params, tokens))) < 1e-4


# ------------------------------------------------------------- rehearsal

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """rehearsal.build's copy, with a tiny Kimi-K2 cell added beside its
    tiny GPT-2 ones: files and entries only."""
    root = rehearsal.build(str(tmp_path_factory.mktemp("bench")))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    m["configs"].append({"name": "kimi-tiny", "source": TINY["source"],
                         "file": "benchmark/configs/kimi-tiny.json",
                         "reduced": [], "why": "CPU rehearsal"})
    m["workloads"].append({"name": "tiny-kimi-sat", "config": "kimi-tiny",
                           "traffic": "tiny-closed", "chips": 1,
                           "why": "CPU rehearsal"})
    for group in ("end_to_end", "per_layer"):
        for metric in m[group]:
            if CELL in metric.get("workloads", ()):
                metric["workloads"].append("tiny-kimi-sat")
    rehearsal._write(path, m)
    rehearsal._write(
        os.path.join(root, "benchmark/configs/kimi-tiny.json"), TINY)
    rehearsal._write(
        os.path.join(root, "benchmark/cells/tiny-kimi-sat.json"),
        {"engine": {"page_size": 4, "num_pages": 128, "max_batch": 4,
                    "max_context": 64}})
    return root


@pytest.mark.parametrize("trace", [0, 1])
def test_serving_cell_rehearsed_on_cpu(root, trace):
    out = rehearsal.run_cell(root, "tiny-kimi-sat", trace=trace)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    line, info = json.loads(lines[-1]), json.loads(lines[-2])["info"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    if not trace:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
        return
    # the counters' readers need no device plane; those that read scopes
    # off a TPU's trace return nothing here
    hit = line["metrics"]["moe.experts_hit.sat"]["value"]
    assert 0 < hit <= 4                             # of the 4 held
    phases = info["detail"]["phases"]
    assert phases["moe_routing_per_run"]["layers"] == 2     # with experts
    rows = phases["attend_rows_per_run"]
    assert rows["kv_row_bytes"] == 128 * 2          # one padded bf16 row
    assert "engine.step_ms.sat" in line["metrics"]
    assert "mla.proj_ms.sat" not in line["metrics"]


# ----------------------------------------------- readers, worked numbers

def test_latent_attention_flops_and_bytes_on_worked_numbers():
    """A position read by the absorbed attention: 64 heads x (512 + 64 for
    the score + 512 for the value) x 2 = 139,264 FLOPs and one row of
    1,280 bytes (108.8 FLOPs a byte).  A decode run of the cell at 16 rows
    of 2,304 positions x 7 layers reads 258,048 rows: 35.9 GFLOP (0.182 ms
    at 197 TFLOP/s) and 330.3 MB (0.403 ms at 819 GB/s): memory bounds it.
    A prefill of the 4,096 bucket, 7 layers: the expansion 2 x 4096 x 512
    x 64 x 256 = 68.7 GFLOP a layer and the triangle 2 x 64 x 4096^2 / 2 x
    320 = 343.6 GFLOP a layer: 2.886 TFLOP, 14.65 ms at the peak."""
    assert mla_flops.absorbed_attend_flops(1, 64, 512, 64) == 139_264
    assert mla_flops.absorbed_attend_bytes(1, 1280) == 1280
    rows = 16 * 2304 * 7
    assert rows == 258_048
    f = mla_flops.absorbed_attend_flops(rows, 64, 512, 64)
    b = mla_flops.absorbed_attend_bytes(rows, 1280)
    assert f == pytest.approx(35.94e9, rel=1e-3)
    assert b == pytest.approx(330.3e6, rel=1e-3)
    assert f / 197e12 < b / 819e9
    shape = dict(layers=7, heads=64, latent=512, nope=128, rope=64, v=128)
    pf = mla_flops.prefill_attend_flops(4096, **shape)
    assert pf == 7 * (2 * 4096 * 512 * 64 * 256
                      + 2 * 64 * 4096 * 4096 / 2 * 320)
    assert pf == pytest.approx(2.886e12, rel=1e-3)
    pb = mla_flops.prefill_attend_bytes(4096, **shape)
    assert pb == 7 * 2 * (4096 * (64 * 640 + 512) + 512 * 64 * 256)
    assert pb / 819e9 < pf / 197e12 / 4             # compute bounds it


def _ctx(tmp_path, decode_scopes, prefill_scopes=()):
    """A capture made by hand: two decode runs and one prefill run (of the
    256 bucket) of jit_fwd, operations under the given scopes (durations
    in ms; a third entry names the instruction where it is a kernel)."""
    from xplane_stats import encode

    from benchmark.harness import peaks

    ms = 1_000_000
    ops, meta = [], {}
    for run_start, scopes in ((10 * ms, decode_scopes),
                              (40 * ms, decode_scopes),
                              (70 * ms, prefill_scopes)):
        for j, (scope, dur, *code) in enumerate(scopes):
            name = f"%op.{len(ops)} = bf16[8] fusion(%x), kind=kLoop" \
                if not code else f"%{code[0]}.{len(ops)} = bf16[8] " \
                f"custom-call(%x)"
            ops.append((name, run_start + j * ms, int(dur * ms)))
            if scope:
                meta[name] = {"tf_op": "jit(fwd)/KimiK2/layer_3/" + scope}
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
                ("llm.prefill", 69 * ms, 28 * ms, {"bucket": 256}),
                ("llm.step", 9 * ms, 29 * ms),
                ("llm.step", 39 * ms, 29 * ms)]})], meta))
    before = {"decode_runs": 0, "kv_rows_read": 0, "kv_rows_held": 0,
              "kv_row_bytes": 1280, "latent_dim": 512, "rope_dim": 64}
    after = dict(before, decode_runs=10, kv_rows_read=10 * 258_048,
                 kv_rows_held=10 * 458_752)
    return {"trace_path": path,
            "sizes": {"n_layer": 6, "kv_layers": 7, "n_head": 64,
                      "kv_lora_rank": 512, "qk_rope_head_dim": 64,
                      "qk_nope_head_dim": 128, "v_head_dim": 128},
            "peaks": peaks.peaks_for("TPU v5 lite"),
            "serve": {"before": {"attention": before},
                      "at_end": {"attention": after}}}


NAMES = ("mla.proj_ms.sat", "mla.attend_roofline.sat",
         "mla.prefill_attend_ms.sat", "mla.prefill_attend_roofline.sat")


def test_readers_on_a_hand_made_capture(tmp_path):
    ctx = _ctx(tmp_path,
               [("attn/mla.q/wq_a/dot_general", 0.3),
                ("attn/mla.kv/wkv_a/dot_general", 0.1),
                ("attn/attn.core/mla.absorb/dot_general", 0.05),
                ("attn/attn.core/kv.store/scatter", 0.02),
                ("attn/attn.core/kv.attend/paged_decode_latent", 0.6,
                 "paged_decode_latent"),
                ("attn/attn.core/mla.absorb/dot_general", 0.05),
                ("attn/attn.out/wo/dot_general", 0.5),
                ("mlp/moe.shared/shared_up/dot_general", 0.2),
                ("mlp/mlp.dense/w_up/dot_general", 0.4),
                ("mlp/moe/moe.experts/ragged_dot", 2)],
               [("attn/mla.q/wq_a/dot_general", 1),
                ("attn/attn.core/kv.store/scatter", 0.5),
                ("attn/attn.core/mla.expand/dot_general", 0.2),
                ("attn/attn.core/transpose", 0.1),
                ("", 0.7, "flash_fwd"),
                ("mlp/moe/moe.experts/ragged_dot", 10)])
    read = {name: manifest.load_reader(name) for name in NAMES}
    # per decode run: the five projections, not the attention, the store,
    # the experts; the prefill run's operations are not a decode run's
    assert read["mla.proj_ms.sat"](ctx) == pytest.approx(1.0)
    # 258,048 rows of 1,280 bytes are 0.4033 ms at 819 GB/s (their 35.9
    # GFLOP 0.182 ms): over the attention's 0.6 ms
    assert read["mla.attend_roofline.sat"](ctx) == pytest.approx(
        100 * 0.40330 / 0.6, rel=1e-3)
    # per prefill run: the expansion, the transposes and the kernel (filed
    # by its name), not the store
    assert read["mla.prefill_attend_ms.sat"](ctx) == pytest.approx(1.0)
    shape = dict(layers=7, heads=64, latent=512, nope=128, rope=64, v=128)
    # at 256 positions the bytes take longer than the FLOPs (a short
    # bucket reads W_kvb for few rows); the reader takes the larger
    least = mla_flops.prefill_attend_bytes(256, **shape) / 819e9
    assert least > mla_flops.prefill_attend_flops(256, **shape) / 197e12
    assert read["mla.prefill_attend_roofline.sat"](ctx) == pytest.approx(
        100 * least / 1e-3, rel=1e-6)
    phases = ctx["info"]["phases"]
    cap = phases["mla_capture"]
    assert (cap["decode_runs"], cap["prefill_runs"]) == (2, 1)
    assert cap["decode_ms_by_scope"]["mla.absorb"] == pytest.approx(0.1)
    assert cap["shared_ms"] == pytest.approx(0.2)
    assert cap["dense_ms"] == pytest.approx(0.4)
    assert cap["prefill_attend_ms_by_bucket"] == {
        "256": [pytest.approx(1.0)]}
    assert phases["mla_attend_roofline"]["bound"] == "memory"
    # the accepted reader files the same kernel
    assert manifest.load_reader("decode.attend_ms.sat")(ctx) == \
        pytest.approx(0.6)


def test_readers_return_nothing_without_names_or_counters(tmp_path):
    """As on a program that has neither the scopes nor the latent
    counters: nothing to read, nothing raised."""
    ctx = _ctx(tmp_path, [("mlp/moe/moe.experts/ragged_dot", 5)])
    ctx["serve"] = {"before": {}, "at_end": {}}
    ctx["sizes"] = {"n_layer": 8}
    for name in NAMES:
        assert manifest.load_reader(name)(ctx) is None
