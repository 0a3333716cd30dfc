"""What PR 41 added to the benchmark, by hand on the CPU: the loader finds
benchmark/families/kimi_linear.py by name and it maps the configuration to
the program's config (the two layer lists, the held share, the router's
width); the configuration file holds every number of the catalog's row;
the reference against the program through the family row;
serve-kimi-linear-48b-a3b-longout rehearsed at a tiny size through
rehearse_run.py (traced and not); the delta rule's FLOPs and bytes on
worked numbers; the four new readers on a hand-made capture."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import rehearsal
from benchmark.harness import kda_flops, manifest
from benchmark.harness.families import family_of

CELL = "serve-kimi-linear-48b-a3b-longout"
TINY = {
    "family": "kimi_linear", "source": "a tiny preset for CPU rehearsals",
    "first_k_dense_replace": 1, "head_dim": 16, "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 96, "kv_lora_rank": 24,
    "linear_attn_config": {
        "full_attn_layers": [4, 6], "head_dim": 16,
        "kda_layers": [1, 2, 3, 5], "num_heads": 4,
        "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 128,
    "model_type": "kimi_linear", "moe_intermediate_size": 32,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 4,
    "num_expert_group": 1, "num_experts": 4, "first_expert": 2,
    "published": {"num_experts": 8}, "num_experts_per_token": 2,
    "num_hidden_layers": 6, "num_key_value_heads": 4,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 16,
    "vocab_size": 512, "kda_gate_rank": 8, "route_norm_eps": 1e-20,
    "l2_norm_eps": 1e-06, "expert_bias_std": 0.005,
    "compute_dtype": "bfloat16", "param_dtype": "bfloat16", "reduced": []}


def test_loader_finds_the_family_file_by_name():
    fam = family_of(TINY)
    assert (fam.name, fam.engine_model, fam.reference) == (
        "kimi_linear", "kimilinear", "kimi_linear_ref")
    cfg = fam.program_config(TINY, attn_impl="dense", remat=False)
    assert cfg.layer_types == ("kda", "kda", "kda", "mla", "kda", "mla")
    assert (cfg.n_experts, cfg.first_expert, cfg.held_experts,
            cfg.experts_per_token, cfg.n_dense_layers, cfg.d_ff,
            cfg.moe_d_ff, cfg.n_shared_experts) == (8, 2, 4, 2, 1, 96, 32, 1)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.mla_use_nope) == (
                None, 24, 16, 8, 16, True)
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv,
            cfg.kda_gate_rank) == (4, 16, 4, 8)
    assert cfg.routed_scaling_factor == 2.446 and cfg.max_seq == 128
    assert cfg.param_dtype == jnp.bfloat16 and not cfg.remat
    # what moe_phases.py divides layer_runs by is the layers WITH experts,
    # its bytes are of the experts HELD; mla_phases.py reads the latent
    # layers' widths, kda_phases.py the delta-rule layers'
    sizes = fam.sizes(TINY)
    assert (sizes["n_layer"], sizes["kv_layers"], sizes["kda_layers"],
            sizes["kda_heads"], sizes["kda_head_dim"], sizes["d_ff"],
            sizes["n_experts"], sizes["router_experts"], sizes["vocab"],
            sizes["n_head"], sizes["kv_lora_rank"],
            sizes["qk_rope_head_dim"], sizes["qk_nope_head_dim"],
            sizes["v_head_dim"], sizes["head_dim"], sizes["max_seq"]) == (
                5, 2, 4, 4, 16, 32, 4, 8, 512, 4, 24, 8, 16, 16, 24, 128)
    for other in ({"num_expert_group": 8}, {"mla_use_nope": False},
                  {"q_lora_rank": 32}, {"tie_word_embeddings": True},
                  {"l2_norm_eps": 0.0},
                  {"moe_router_activation_func": "softmax"}):
        with pytest.raises(ValueError, match="source's choices"):
            fam.program_config(dict(TINY, **other))
    overlap = dict(TINY, linear_attn_config=dict(
        TINY["linear_attn_config"], full_attn_layers=[3, 4, 6]))
    with pytest.raises(ValueError, match="part the layers"):
        fam.program_config(overlap)


def test_published_config_holds_every_catalog_number():
    cell = manifest.load_cell(CELL)
    catalog = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 9216,
        "kv_lora_rank": 512,
        "linear_attn_config": {
            "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
            "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18,
                           19, 21, 22, 23, 25, 26],
            "num_heads": 32, "short_conv_kernel_size": 4},
        "mla_use_nope": True, "model_max_length": 1048576,
        "model_type": "kimi_linear", "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid",
        "num_attention_heads": 32, "num_expert_group": 1,
        "num_experts": 256, "num_experts_per_token": 8,
        "num_hidden_layers": 27, "num_key_value_heads": 32,
        "num_nextn_predict_layers": 0, "num_shared_experts": 1,
        "q_lora_rank": None, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000,
        "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
        "vocab_size": 163840}
    differ = {k for k, v in catalog.items() if cell.config.get(k, "-") != v}
    assert differ == set(cell.config["reduced"]) == {"num_experts"}
    assert cell.config["published"]["num_experts"] == 256
    assert cell.config["num_experts"] == 16
    assert set(cell.config["reduced_why"]) == set(cell.config["reduced"])
    assert {"kda_gate_rank", "A_log_dt_bias", "conv", "l2_norm_eps",
            "initializer_range", "expert_bias", "grouping", "compute_dtype",
            "state_row"} <= set(cell.config["assumed"])
    assert "16 chips" in cell.config["deployment"]
    fam = family_of(cell.config)
    cfg = fam.program_config(cell.config)
    assert (cfg.n_layer, cfg.n_moe_layers, cfg.layers_of("kda"),
            cfg.layers_of("mla"), cfg.n_experts, cfg.first_expert,
            cfg.held_experts, cfg.vocab_size) == (
                27, 26, 20, 7, 256, 0, 16, 163840)
    sizes = fam.sizes(cell.config)
    assert (sizes["n_layer"], sizes["n_experts"], sizes["d_ff"],
            sizes["kv_layers"], sizes["kda_layers"]) == (26, 16, 1024, 7, 20)
    # the traffic: offline-closed-4k's file with the issue's parameters
    t = cell.traffic
    assert (t["clients"], t["pool"], t["order_block"], t["order_seed"],
            t["fill_limit_s"], t["max_total"], t["shared_prefix_tokens"]
            ) == (24, 384, 24, 23, 60, 4096, 0)
    assert t["prompt_len"] == {"dist": "lognormal", "median": 512,
                               "sigma": 0.8, "min": 128, "max": 2048}
    assert t["output_len"] == {"dist": "lognormal", "median": 768,
                               "sigma": 0.7, "min": 64, "max": 3072}
    base = json.load(open(os.path.join(
        manifest.BENCH_DIR, "traffic", "offline-closed-4k.json")))
    assert t["sampling"] == base["sampling"]
    assert (t["check"]["greedy_sample"], t["check"]["max_positions"]
            ) == (2, 1024)
    # why_max_positions' count, made again from the generator
    from benchmark.harness import traffic

    pool = traffic._sizes(t, 384, np.random.default_rng([23, 0x7261]))
    short = [i for i, s in enumerate(pool) if s["temperature"] == 0
             and s["prompt_len"] + s["max_tokens"] <= 1024]
    assert (len(short), sum(i < 40 for i in short)) == (79, 9)
    assert "79 of" in t["check"]["why_max_positions"]
    assert max(s["prompt_len"] + s["max_tokens"] for s in pool) <= 4096
    assert traffic.prefill_buckets(t) == [128, 256, 512, 1024, 2048]
    assert cell.settings["engine"] == {
        "page_size": 16, "num_pages": 4096, "max_batch": 16,
        "prefill_token_budget": 2064, "max_context": 4096}
    assert cell.chips == 1
    sized = cell.settings["sized"]["programs"]
    assert set(sized) == {"decode", "prefill[128]", "prefill[256]",
                          "prefill[512]", "prefill[1024]", "prefill[2048]"}
    assert max(p["total_GB"] for p in sized.values()) < 16.9 * 0.85


def test_reference_against_program_through_the_family_row():
    """bf16 weights, float32 compute on both sides: the same equations,
    with the share of the experts (4 of 8, from the third) on both."""
    from benchmark.reference import kimi_linear_ref as ref
    from ray_tpu.models.kimi_linear import KimiLinear

    config = dict(TINY, compute_dtype="float32")
    fam = family_of(config)
    cfg = fam.program_config(config, attn_impl="dense", remat=False)
    params = fam.init(cfg, jax.random.PRNGKey(3))
    f32 = ("expert_bias", "A_log", "dt_bias", "conv_w")
    leaves = jax.tree_util.tree_leaves_with_path(params)
    assert all(x.dtype == (jnp.float32 if path[-1].key in f32
                           else jnp.bfloat16) for path, x in leaves)
    moe = params["params"]["layer_2"]["moe"]
    assert moe["w_up"].shape == (4, 64, 32)         # the held ones
    assert moe["router"].shape == (64, 8)           # all of them
    assert float(jnp.std(moe["expert_bias"])) > 0.001   # not zero
    assert "moe" not in params["params"]["layer_0"]     # the dense layer
    kda = params["params"]["layer_0"]["kda"]
    assert float(jnp.min(kda["A_log"])) >= 0 \
        and float(jnp.max(kda["A_log"])) <= float(jnp.log(16.0))
    params = jax.tree_util.tree_map_with_path(
        lambda path, w: w if w.ndim == 1 or path[-1].key == "conv_w"
        else 8 * w, params)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, 512, (2, 41)), jnp.int32)
    ours = KimiLinear(cfg).apply(params, tokens[:, :-1])
    want = ref.forward(config, params, tokens[:, :-1])
    assert float(jnp.std(want)) > 0.05
    assert float(jnp.max(jnp.abs(ours - want))) < 2e-4
    loss = fam.loss(cfg, params, {"tokens": tokens})
    assert abs(float(loss) - float(ref.loss(config, params, tokens))) < 1e-4


# ------------------------------------------------------------- rehearsal

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """rehearsal.build's copy, with a tiny Kimi-Linear cell added beside
    its tiny GPT-2 ones: files and entries only."""
    root = rehearsal.build(str(tmp_path_factory.mktemp("bench")))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    m["configs"].append({"name": "kimi-linear-tiny",
                         "source": TINY["source"],
                         "file": "benchmark/configs/kimi-linear-tiny.json",
                         "reduced": [], "why": "CPU rehearsal"})
    m["workloads"].append({"name": "tiny-kimi-linear-sat",
                           "config": "kimi-linear-tiny",
                           "traffic": "tiny-closed", "chips": 1,
                           "why": "CPU rehearsal"})
    for group in ("end_to_end", "per_layer"):
        for metric in m[group]:
            if CELL in metric.get("workloads", ()):
                metric["workloads"].append("tiny-kimi-linear-sat")
    rehearsal._write(path, m)
    rehearsal._write(os.path.join(
        root, "benchmark/configs/kimi-linear-tiny.json"), TINY)
    rehearsal._write(
        os.path.join(root, "benchmark/cells/tiny-kimi-linear-sat.json"),
        {"engine": {"page_size": 4, "num_pages": 128, "max_batch": 4,
                    "max_context": 64}})
    return root


@pytest.mark.parametrize("trace", [0, 1])
def test_serving_cell_rehearsed_on_cpu(root, trace):
    out = rehearsal.run_cell(root, "tiny-kimi-linear-sat", trace=trace)
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
    assert phases["moe_routing_per_run"]["layers"] == 5     # with experts
    rows = phases["attend_rows_per_run"]
    assert rows["kv_row_bytes"] == 128 * 2          # one padded bf16 row
    assert "engine.step_ms.sat" in line["metrics"]
    assert "kda.mixer_ms.sat" not in line["metrics"]


# ----------------------------------------------- readers, worked numbers

def test_delta_rule_flops_and_bytes_on_worked_numbers():
    """A decode run of the cell, 16 rows x 20 layers = 320 slots: the
    recurrence moves 320 x 2,097,152 B x 2 = 1.342 GB (1.639 ms at 819
    GB/s); the whole mixers 20 x 79.02 MB of weights + 320 x 2,170,880 B x
    2 = 2.970 GB (3.626 ms).  A token of a prefill costs a head 7 x 128 x
    128 = 114,688 FLOPs: 3.67 MFLOP a layer over 32 heads, and 12 x 4,096 =
    49,152 bytes: at the 2,048 bucket and 20 layers 150.3 GFLOP (0.763 ms
    at 197 TFLOP/s) and 2.013 GB (2.458 ms): the memory bounds it."""
    assert kda_flops.step_bytes(1, 32, 128) == 2 * 2_097_152
    assert kda_flops.step_bytes(320, 32, 128) == pytest.approx(1.3422e9,
                                                               rel=1e-4)
    assert kda_flops.least_ms(kda_flops.step_bytes(320, 32, 128),
                              819e9) == pytest.approx(1.6388, rel=1e-4)
    weights = 39_510_016 * 2
    whole = kda_flops.decode_mixer_bytes(320, 2_170_880, weights, 20)
    assert whole == 20 * weights + 2 * 320 * 2_170_880
    assert kda_flops.least_ms(whole, 819e9) == pytest.approx(3.626, rel=1e-3)
    assert kda_flops.scan_flops(1, 1, 1, 128) == 114_688
    f = kda_flops.scan_flops(2048, 20, 32, 128)
    b = kda_flops.scan_bytes(2048, 20, 32, 128)
    assert f == pytest.approx(150.3e9, rel=1e-3)
    assert b == 12 * 4096 * 20 * 2048 == pytest.approx(2.013e9, rel=1e-3)
    assert f / 197e12 < b / 819e9


def _ctx(tmp_path, decode_scopes, prefill_scopes=()):
    """A capture made by hand: two decode runs and one prefill run (of the
    256 bucket) of jit_fwd, operations under the given scopes (durations
    in ms; a third entry is the whole instruction where its name
    matters)."""
    from xplane_stats import encode

    from benchmark.harness import peaks

    ms = 1_000_000
    ops, meta = [], {}
    for run_start, scopes in ((10 * ms, decode_scopes),
                              (40 * ms, decode_scopes),
                              (70 * ms, prefill_scopes)):
        for j, (scope, dur, *code) in enumerate(scopes):
            name = f"%op.{len(ops)} = bf16[8] fusion(%x), kind=kLoop" \
                if not code else code[0].format(n=len(ops))
            ops.append((name, run_start + j * ms, int(dur * ms)))
            if scope:
                meta[name] = {"tf_op": "jit(fwd)/KimiLinear/layer_2/" + scope}
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
    before = {"decode_runs": 0, "state_rows_updated": 0,
              "state_row_bytes": 2_170_880,
              "mixer_weight_bytes": 39_510_016 * 2, "slots_used": 16,
              "slots_total": 16}
    # 12 of the 16 rows ran: the step still moves all 16 slots a layer
    after = dict(before, decode_runs=10, state_rows_updated=10 * 240)
    return {"trace_path": path,
            "sizes": {"n_layer": 26, "kv_layers": 7, "kda_layers": 20,
                      "kda_heads": 32, "kda_head_dim": 128},
            "peaks": peaks.peaks_for("TPU v5 lite"),
            "serve": {"before": {"state": before},
                      "at_end": {"state": after}}}


NAMES = ("kda.mixer_ms.sat", "kda.step_roofline.sat", "kda.scan_ms.sat",
         "kda.scan_roofline.sat")
STATE_COPY = "%copy-done.{n} = f32[16,32,128,128]{{3,2,1,0}} " \
    "copy-done(%copy-start.1)"
OTHER_COPY = "%copy-done.{n} = bf16[2304,4096]{{1,0}} copy-done(%cs.2)"


def test_readers_on_a_hand_made_capture(tmp_path):
    ctx = _ctx(tmp_path,
               [("kda/kda.proj/wq/dot_general", 0.3),
                ("kda/kda.conv/scatter", 0.1),
                ("kda/kda.gate/exp", 0.05),
                ("kda/kda.step/gather", 1.0),
                ("kda/kda.step/scatter", 2.0),
                ("", 0.2, STATE_COPY),
                ("", 0.4, OTHER_COPY),
                ("kda/kda.out_norm/o_norm/mul", 0.05),
                ("kda/kda.out_proj/wo/dot_general", 0.5),
                ("attn/attn.out/wo/dot_general", 0.7),
                ("mlp/moe.shared/shared_up/dot_general", 0.2),
                ("mlp/mlp.dense/w_up/dot_general", 0.4),
                ("mlp/moe/moe.experts/ragged_dot", 2)],
               [("kda/kda.proj/wq/dot_general", 1),
                ("kda/kda.scan/triangular_solve", 3),
                ("kda/kda.scan/while/body/dot_general", 1),
                ("attn/attn.out/wo/dot_general", 2),
                ("mlp/moe/moe.experts/ragged_dot", 10)])
    read = {name: manifest.load_reader(name) for name in NAMES}
    # per decode run: the seven scopes, not the latent layers' attn.out,
    # the shared expert, the experts or an unscoped copy
    assert read["kda.mixer_ms.sat"](ctx) == pytest.approx(4.0)
    # 320 slots x 2,097,152 B x 2 at 819 GB/s = 1.6388 ms over the 3.0 ms
    # under kda.step + the 0.2 ms of a state-shaped copy (not the weight's)
    assert read["kda.step_roofline.sat"](ctx) == pytest.approx(
        100 * 1.63879 / 3.2, rel=1e-4)
    assert read["kda.scan_ms.sat"](ctx) == pytest.approx(4.0)
    least = kda_flops.scan_bytes(256, 20, 32, 128) / 819e9
    assert read["kda.scan_roofline.sat"](ctx) == pytest.approx(
        100 * least / 4e-3, rel=1e-6)
    phases = ctx["info"]["phases"]
    cap = phases["kda_capture"]
    assert (cap["decode_runs"], cap["prefill_runs"]) == (2, 1)
    assert cap["ms_by_scope"]["kda.step"] == pytest.approx(3.0)
    assert cap["state_copy_ms"] == pytest.approx(0.2)
    assert cap["shared_ms"] == pytest.approx(0.2)
    assert cap["dense_ms"] == pytest.approx(0.4)
    assert cap["scan_ms_by_bucket"] == {"256": [pytest.approx(4.0)]}
    step = phases["kda_step_roofline"]
    assert (step["rows_moved"], step["rows_running"]) == (320, 240)
    floor = phases["kda_mixer_floor"]
    assert floor["least_ms"] == pytest.approx(3.626, rel=1e-3)
    assert phases["kda_scan_roofline"]["bound"] == "memory"
    # the accepted reader that sums the latent layers' projections reads
    # attn.out and none of the KDA layers' scopes
    ctx["sizes"].update(n_head=32, kv_lora_rank=512, qk_rope_head_dim=64,
                        qk_nope_head_dim=128, v_head_dim=128)
    assert manifest.load_reader("mla.proj_ms.sat")(ctx) is None \
        or manifest.load_reader("mla.proj_ms.sat")(ctx) == \
        pytest.approx(0.7)


def test_a_kernel_named_kda_step_is_filed_by_its_name(tmp_path):
    ctx = _ctx(tmp_path, [
        ("kda/kda.proj/wq/dot_general", 0.5),
        ("", 2.0, "%kda_step.{n} = f32[16,32,128] custom-call(%x), "
         "custom_call_target=\"tpu_custom_call\"")])
    assert manifest.load_reader("kda.step_roofline.sat")(ctx) == \
        pytest.approx(100 * 1.63879 / 2.0, rel=1e-4)


def test_readers_return_nothing_without_names_or_counters(tmp_path):
    """As on a program that has neither the scopes nor the counters, or a
    family file without the delta-rule sizes (the parent's): nothing to
    read, nothing raised."""
    ctx = _ctx(tmp_path, [("mlp/moe/moe.experts/ragged_dot", 5)])
    for name in NAMES:
        assert manifest.load_reader(name)(ctx) is None
    ctx = _ctx(tmp_path, [("kda/kda.step/gather", 1.0)])
    ctx["serve"] = {"before": {}, "at_end": {}}
    assert manifest.load_reader("kda.step_roofline.sat")(ctx) is None
    assert manifest.load_reader("kda.mixer_ms.sat")(ctx) == \
        pytest.approx(1.0)
    ctx["sizes"] = {"n_layer": 8}
    ctx.pop("_kda_capture")
    for name in NAMES:
        assert manifest.load_reader(name)(ctx) is None
