"""What PR 33 added to the benchmark, by hand on the CPU: the loader finds
benchmark/families/lfm2_moe.py by name and it maps the configuration to
the program's config; the configuration file holds every number of the
catalog's row; the reference against the program through the family row;
serve-lfm2-24b-a2b-sat rehearsed at a tiny size through rehearse_run.py
(traced and not); the three new readers on a hand-made capture; the
mixers' bytes on worked numbers."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import rehearsal
from benchmark.harness import conv_flops, manifest
from benchmark.harness.families import family_of

CELL = "serve-lfm2-24b-a2b-sat"
TINY = {
    "family": "lfm2_moe", "source": "a tiny preset for CPU rehearsals",
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 64,
    "intermediate_size": 96,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv"],
    "max_position_embeddings": 128, "model_type": "lfm2_moe",
    "moe_intermediate_size": 32, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_dense_layers": 2, "num_experts": 8,
    "num_experts_per_tok": 2, "num_hidden_layers": 5,
    "num_key_value_heads": 2,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 512,
    "tie_word_embeddings": True, "route_norm_eps": 1e-06,
    "expert_bias_std": 0.02, "compute_dtype": "bfloat16",
    "param_dtype": "bfloat16", "reduced": []}


def test_loader_finds_the_family_file_by_name():
    fam = family_of(TINY)
    assert (fam.name, fam.engine_model, fam.reference) == (
        "lfm2_moe", "lfm2moe", "lfm2_moe_ref")
    cfg = fam.program_config(TINY, attn_impl="dense", remat=False)
    assert (cfg.n_experts, cfg.held_experts, cfg.experts_per_token,
            cfg.n_dense_layers, cfg.d_ff, cfg.moe_d_ff, cfg.conv_taps) == (
        8, None, 2, 2, 96, 32, 3)
    assert cfg.layer_types == ("conv", "conv", "full_attention", "conv",
                               "conv")
    assert cfg.rope_theta == 1e6
    assert cfg.param_dtype == jnp.bfloat16 and not cfg.remat
    # what moe_phases.py divides layer_runs by is the layers WITH experts
    sizes = fam.sizes(TINY)
    assert (sizes["n_layer"], sizes["d_ff"], sizes["n_experts"],
            sizes["conv_layers"], sizes["kv_layers"], sizes["vocab"]) == (
        3, 32, 8, 4, 1, 512)
    for other in ({"conv_bias": True}, {"route_norm_eps": 0.0}):
        with pytest.raises(ValueError, match="source's choices"):
            fam.program_config(dict(TINY, **other))


def test_published_config_holds_every_catalog_number():
    cell = manifest.load_cell(CELL)
    layer_types = [("full_attention" if i % 4 == 2 else "conv")
                   for i in range(40)]
    catalog = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "layer_types": layer_types,
        "max_position_embeddings": 128000, "model_type": "lfm2_moe",
        "moe_intermediate_size": 1536, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
        "num_hidden_layers": 40, "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 65536}
    differ = {k for k, v in catalog.items() if cell.config.get(k, "-") != v}
    assert differ == set(cell.config["reduced"]) == {
        "num_hidden_layers", "layer_types"}
    published = cell.config["published"]
    assert all(published[k] == catalog[k]
               for k in ("num_hidden_layers", "layer_types"))
    assert cell.config["layer_types"] == layer_types[:10]
    assert cell.config["num_hidden_layers"] == 10
    assert set(cell.config["reduced_why"]) == set(cell.config["reduced"])
    assert {"tie_word_embeddings", "route_norm_eps", "initializer_range",
            "expert_bias", "conv_taps", "window_dtype"} <= set(
        cell.config["assumed"])
    fam = family_of(cell.config)
    cfg = fam.program_config(cell.config)
    assert (cfg.n_experts, cfg.held_experts, cfg.experts_per_token,
            cfg.layers_of("conv"), cfg.layers_of("full_attention"),
            cfg.n_moe_layers) == (64, None, 4, 8, 2, 8)
    assert fam.sizes(cell.config)["n_layer"] == 8
    # the traffic: offline-closed-384 in everything but the pool and check
    assert cell.traffic["pool"] == 768
    base = json.load(open(os.path.join(
        manifest.BENCH_DIR, "traffic", "offline-closed-384.json")))
    assert {k for k in base if base[k] != cell.traffic[k]} == {
        "pool", "why_pool", "check"}
    assert {k for k in base["check"]
            if base["check"][k] != cell.traffic["check"][k]} == {
        "logit_tolerance", "reason"}
    assert cell.settings["engine"] == {
        "page_size": 16, "num_pages": 1024, "max_batch": 16,
        "prefill_token_budget": 1088, "max_context": 1024}


def test_reference_against_program_through_the_family_row():
    """bf16 weights, float32 compute on both sides: the same equations."""
    from benchmark.reference import lfm2_moe_ref as ref
    from ray_tpu.models.lfm2 import Lfm2

    config = dict(TINY, compute_dtype="float32")
    fam = family_of(config)
    cfg = fam.program_config(config, attn_impl="dense", remat=False)
    params = fam.init(cfg, jax.random.PRNGKey(3))
    leaves = jax.tree_util.tree_leaves_with_path(params)
    assert all(x.dtype == (jnp.float32 if path[-1].key == "expert_bias"
                           else jnp.bfloat16) for path, x in leaves)
    moe = params["params"]["layer_2"]["moe"]
    assert moe["w_up"].shape == (8, 64, 32)
    assert float(jnp.std(moe["expert_bias"])) > 0.005   # not zero
    assert "moe" not in params["params"]["layer_1"]     # a dense layer
    params = jax.tree_util.tree_map_with_path(
        lambda path, w: w if w.ndim == 1 or path[-1].key == "conv_w"
        else 8 * w, params)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, 512, (2, 33)), jnp.int32)
    ours = Lfm2(cfg).apply(params, tokens[:, :-1])
    want = ref.forward(config, params, tokens[:, :-1])
    assert float(jnp.std(want)) > 0.05
    assert float(jnp.max(jnp.abs(ours - want))) < 1e-4
    loss = fam.loss(cfg, params, {"tokens": tokens})
    assert abs(float(loss) - float(ref.loss(config, params, tokens))) < 1e-4


# ------------------------------------------------------------- rehearsal

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """rehearsal.build's copy, with a tiny LFM2 cell added beside its tiny
    GPT-2 ones: files and entries only."""
    root = rehearsal.build(str(tmp_path_factory.mktemp("bench")))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    m["configs"].append({"name": "lfm2-tiny", "source": TINY["source"],
                         "file": "benchmark/configs/lfm2-tiny.json",
                         "reduced": [], "why": "CPU rehearsal"})
    m["workloads"].append({"name": "tiny-lfm2-sat", "config": "lfm2-tiny",
                           "traffic": "tiny-closed", "chips": 1,
                           "why": "CPU rehearsal"})
    for group in ("end_to_end", "per_layer"):
        for metric in m[group]:
            if CELL in metric.get("workloads", ()):
                metric["workloads"].append("tiny-lfm2-sat")
    rehearsal._write(path, m)
    rehearsal._write(
        os.path.join(root, "benchmark/configs/lfm2-tiny.json"), TINY)
    rehearsal._write(
        os.path.join(root, "benchmark/cells/tiny-lfm2-sat.json"),
        {"engine": {"page_size": 4, "num_pages": 128, "max_batch": 4,
                    "max_context": 64}})
    return root


@pytest.mark.parametrize("trace", [0, 1])
def test_serving_cell_rehearsed_on_cpu(root, trace):
    out = rehearsal.run_cell(root, "tiny-lfm2-sat", trace=trace)
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
    assert 0 < hit <= 8
    phases = info["detail"]["phases"]
    assert phases["moe_routing_per_run"]["layers"] == 3     # with experts
    assert float(phases["moe_routing_per_run"]["runs"]).is_integer()
    assert "engine.step_ms.sat" in line["metrics"]
    assert "conv.mixer_ms.sat" not in line["metrics"]


# ----------------------------------------------- readers, worked numbers

ROW = 2 * 2048 * 2                      # a window: two rows of bf16
WEIGHTS = (4 * 2048 * 2048 + 3 * 2048) * 2


def test_mixer_bytes_on_worked_numbers():
    """One decode run of the cell: the mixers' weights 8 x (2048 x 6144 +
    2048 x 2048 + 3 x 2048) x 2 bytes = 268.5 MB; 16 rows x 8 short-conv
    layers = 128 windows of 8,192 bytes, read and written once: 2.1 MB;
    270.6 MB are 0.330 ms at 819 GB/s."""
    assert (ROW, WEIGHTS) == (8_192, 33_554_432 + 12_288)
    nbytes = conv_flops.decode_mixer_bytes(128, ROW, WEIGHTS, 8)
    assert nbytes == 8 * WEIGHTS + 2 * 128 * ROW == 270_630_912
    assert conv_flops.least_ms(nbytes, 819e9) == pytest.approx(0.3304,
                                                               abs=1e-3)


def _ctx(tmp_path, decode_scopes, prefill_scopes=()):
    """A capture made by hand: two decode runs and one prefill run of
    jit_fwd, operations under the given scopes (durations in ms)."""
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
                f"{code[0]}(%x)"
            ops.append((name, run_start + 2 * j * ms, int(dur * ms)))
            if scope:       # an asynchronous copy carries none
                meta[name] = {"tf_op": "jit(fwd)/Lfm2/layer_3/" + scope}
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
    before = {"slots_total": 16, "slots_used": 16, "decode_runs": 0,
              "state_rows_updated": 0, "state_row_bytes": ROW,
              "mixer_weight_bytes": WEIGHTS}
    after = dict(before, decode_runs=10, state_rows_updated=10 * 128)
    return {"trace_path": path, "sizes": {"n_layer": 8, "conv_layers": 8},
            "peaks": peaks.peaks_for("TPU v5 lite"),
            "serve": {"before": {"state": before},
                      "at_end": {"state": after}}}


NAMES = ("conv.mixer_ms.sat", "mlp.dense_ms.sat")


def test_readers_on_a_hand_made_capture(tmp_path):
    ctx = _ctx(tmp_path,
               [("conv/conv.in_proj/in_proj/dot_general", 0.25),
                ("conv/conv.gate/mul", 0.03),
                ("conv/conv.window/scatter", 0.06),
                ("conv/conv.gate/mul", 0.02),
                ("conv/conv.out_proj/out_proj/dot_general", 0.14),
                ("mlp/mlp.dense/w_up/dot_general", 0.4),
                ("mlp/moe/moe.experts/ragged_dot", 7),
                ("", 0.16, "copy-done")],
               [("conv/conv.in_proj/in_proj/dot_general", 3)])
    read = {name: manifest.load_reader(name) for name in NAMES}
    # per decode run; the prefill run's operations are not a decode run's
    assert read["conv.mixer_ms.sat"](ctx) == pytest.approx(0.5)
    assert read["mlp.dense_ms.sat"](ctx) == pytest.approx(0.4)
    phases = ctx["info"]["phases"]
    # beside the time, never over it: 270.6 MB are 0.330 ms at the peak,
    # and the run's asynchronous copies (no scope) are told apart
    assert phases["conv_mixer_floor"] == {
        "bytes": 270_630_912, "scoped_ms": pytest.approx(0.5),
        "least_ms": pytest.approx(0.3304, abs=1e-3)}
    assert phases["conv_capture"]["async_copy_ms_by_op"] == {
        "copy-done": pytest.approx(0.16)}
    assert phases["conv_capture"]["async_copy_ms"] == pytest.approx(0.16)
    assert phases["conv_capture"]["ms_by_scope"]["conv.gate"] == \
        pytest.approx(0.05)
    assert phases["conv_capture"]["decode_runs"] == 2
    assert phases["ssm_state_rows_per_run"]["state_rows_updated"] == 128


def test_readers_return_nothing_without_names_or_counters(tmp_path):
    """As on a program that has neither the scopes nor stats()["state"]:
    nothing to read, nothing raised."""
    ctx = _ctx(tmp_path, [("mlp/moe/moe.experts/ragged_dot", 5)])
    ctx["serve"] = {"before": {}, "at_end": {}}
    for name in NAMES:
        assert manifest.load_reader(name)(ctx) is None
