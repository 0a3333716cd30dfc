"""What PR 31 added to the benchmark, by hand on the CPU: the loader finds
benchmark/families/granitemoehybrid.py by name; the configuration file
holds every number of the catalog's row; the reference against the
program through the family row (a share of the experts held);
serve-granite-4.0-h-small-sat rehearsed at a tiny size through
rehearse_run.py (traced and not); the four new readers on a hand-made
capture; the mixers' roofline on worked numbers."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import rehearsal
from benchmark.harness import manifest, ssm_flops
from benchmark.harness.families import family_of

CELL = "serve-granite-4.0-h-small-sat"
TINY = {
    "family": "granitemoehybrid",
    "source": "a tiny preset for CPU rehearsals",
    "attention_bias": False, "attention_multiplier": 0.0625,
    "embedding_multiplier": 12, "hidden_size": 64, "intermediate_size": 32,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "logits_scaling": 16, "mamba_chunk_size": 8, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_expand": 1, "mamba_n_groups": 1, "mamba_n_heads": 4,
    "mamba_proj_bias": False, "max_position_embeddings": 128,
    "num_attention_heads": 4, "num_experts_per_tok": 2,
    "num_hidden_layers": 4, "num_key_value_heads": 2,
    "num_local_experts": 3, "first_local_expert": 2,
    "published": {"num_local_experts": 8},
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "shared_intermediate_size": 32,
    "tie_word_embeddings": True, "vocab_size": 512,
    "compute_dtype": "bfloat16", "param_dtype": "bfloat16", "reduced": []}


def test_loader_finds_the_family_file_by_name():
    fam = family_of(TINY)
    assert (fam.name, fam.engine_model, fam.reference) == (
        "granitemoehybrid", "granitemoehybrid", "granitemoehybrid_ref")
    cfg = fam.program_config(TINY, attn_impl="dense", remat=False)
    assert (cfg.n_experts, cfg.first_expert, cfg.held_experts,
            cfg.experts_per_token) == (8, 2, 3, 2)
    assert cfg.layer_types == ("mamba", "mamba", "attention", "mamba")
    assert cfg.param_dtype == jnp.bfloat16 and not cfg.remat
    sizes = fam.sizes(TINY)     # what moe_phases.py and ssm_phases.py read
    assert (sizes["n_layer"], sizes["d_ff"], sizes["n_experts"],
            sizes["ssm_layers"], sizes["vocab"]) == (4, 32, 3, 3, 512)
    with pytest.raises(ValueError, match="source's choices"):
        fam.program_config(dict(TINY, position_embedding_type="rope"))


def test_published_config_holds_every_catalog_number():
    cell = manifest.load_cell(CELL)
    layer_types = [("attention" if i % 10 == 5 else "mamba")
                   for i in range(40)]
    catalog = {
        "attention_bias": False, "attention_multiplier": 0.0078125,
        "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 4096, "intermediate_size": 768,
        "layer_types": layer_types, "logits_scaling": 16,
        "mamba_chunk_size": 256, "mamba_conv_bias": True,
        "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
        "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128,
        "mamba_proj_bias": False, "max_position_embeddings": 131072,
        "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 10, "num_hidden_layers": 40,
        "num_key_value_heads": 8, "num_local_experts": 72,
        "position_embedding_type": "nope", "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 1536, "tie_word_embeddings": True,
        "vocab_size": 100352}
    differ = {k for k, v in catalog.items() if cell.config.get(k, "-") != v}
    assert differ == set(cell.config["reduced"]) == {
        "num_hidden_layers", "layer_types", "num_local_experts"}
    # what was cut is stated beside it, and the cut is whole periods
    published = cell.config["published"]
    assert all(published[k] == catalog[k] for k in (
        "num_hidden_layers", "layer_types", "num_local_experts",
        "num_experts_per_tok"))
    assert cell.config["layer_types"] == layer_types[:20]
    assert (cell.config["num_hidden_layers"],
            cell.config["num_local_experts"]) == (20, 18)
    assert set(cell.config["reduced_why"]) == set(cell.config["reduced"])
    cfg = family_of(cell.config).program_config(cell.config)
    assert (cfg.n_experts, cfg.held_experts, cfg.experts_per_token,
            cfg.layers_of("mamba"), cfg.layers_of("attention")) == (
        72, 18, 10, 18, 2)
    assert cell.traffic["pool"] == 384
    base = json.load(open(os.path.join(
        manifest.BENCH_DIR, "traffic", "offline-closed.json")))
    assert {k for k in base if base[k] != cell.traffic[k]} == {
        "pool", "why_pool", "check"}
    assert {k for k in base["check"]
            if base["check"][k] != cell.traffic["check"][k]} == {
        "logit_tolerance", "reason"}
    assert cell.settings["engine"] == {
        "page_size": 16, "num_pages": 1024, "max_batch": 16,
        "prefill_token_budget": 1088, "max_context": 1024}


def test_reference_against_program_through_the_family_row():
    """bf16 weights, float32 compute on both sides, experts 2-4 of 8
    held: the same equations, the same share."""
    from benchmark.reference import granitemoehybrid_ref as ref
    from ray_tpu.models.granite import Granite

    config = dict(TINY, compute_dtype="float32")
    fam = family_of(config)
    cfg = fam.program_config(config, attn_impl="dense", remat=False)
    params = fam.init(cfg, jax.random.PRNGKey(3))
    leaves = jax.tree_util.tree_leaves_with_path(params)
    assert {x.dtype for _, x in leaves} == {jnp.dtype(jnp.bfloat16),
                                            jnp.dtype(jnp.float32)}
    assert all(x.dtype == jnp.bfloat16 for path, x in leaves
               if path[-1].key not in ("A_log", "D", "dt_bias", "conv_w",
                                       "conv_b"))
    assert params["params"]["layer_0"]["moe"]["w_up"].shape == (3, 64, 32)
    params = jax.tree_util.tree_map_with_path(
        lambda path, w: w if w.ndim == 1 else 8 * w * (
            12 if path[-1].key == "embed" else 1), params)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, 512, (2, 33)), jnp.int32)
    ours = Granite(cfg).apply(params, tokens[:, :-1])
    want = ref.forward(config, params, tokens[:, :-1])
    assert float(jnp.std(want)) > 0.05
    assert float(jnp.max(jnp.abs(ours - want))) < 1e-4
    loss = fam.loss(cfg, params, {"tokens": tokens})
    assert abs(float(loss) - float(ref.loss(config, params, tokens))) < 1e-4


# ------------------------------------------------------------- rehearsal

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """rehearsal.build's copy, with a tiny Granite cell added beside its
    tiny GPT-2 ones: files and entries only."""
    root = rehearsal.build(str(tmp_path_factory.mktemp("bench")))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    m["configs"].append({"name": "granite-tiny", "source": TINY["source"],
                         "file": "benchmark/configs/granite-tiny.json",
                         "reduced": [], "why": "CPU rehearsal"})
    m["workloads"].append({"name": "tiny-granite-sat",
                           "config": "granite-tiny",
                           "traffic": "tiny-closed", "chips": 1,
                           "why": "CPU rehearsal"})
    for group in ("end_to_end", "per_layer"):
        for metric in m[group]:
            if CELL in metric.get("workloads", ()):
                metric["workloads"].append("tiny-granite-sat")
    rehearsal._write(path, m)
    rehearsal._write(
        os.path.join(root, "benchmark/configs/granite-tiny.json"), TINY)
    rehearsal._write(
        os.path.join(root, "benchmark/cells/tiny-granite-sat.json"),
        {"engine": {"page_size": 4, "num_pages": 128, "max_batch": 4,
                    "max_context": 64}})
    return root


@pytest.mark.parametrize("trace", [0, 1])
def test_serving_cell_rehearsed_on_cpu(root, trace):
    out = rehearsal.run_cell(root, "tiny-granite-sat", trace=trace)
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
    assert 0 < hit <= 3                     # of the 3 experts held
    phases = info["detail"]["phases"]
    assert phases["moe_routing_per_run"]["layers"] == 4
    assert phases["moe_routing_per_run"]["pairs"] < 4 * 2 * 4
    assert "engine.step_ms.sat" in line["metrics"]
    assert "ssm.mixer_ms.sat" not in line["metrics"]


# ----------------------------------------------- readers, worked numbers

def test_mixer_bytes_on_worked_numbers():
    """One decode run of the cell: 16 rows x 18 state-space layers = 288
    state rows of 3 x 8448 bf16 + 128 x 64 x 128 float32 = 4,244,992
    bytes, read and written once: 2.445 GB; the mixers' matrices 18 x
    4096 x (8192 + 8448 + 128 + 8192) x 2 bytes = 3.680 GB; 6.125 GB are
    7.48 ms at 819 GB/s."""
    row = 3 * 8448 * 2 + 128 * 64 * 128 * 4
    weights = 4096 * (8192 + 8448 + 128 + 8192) * 2
    assert (row, weights) == (4_244_992, 204_472_320)
    nbytes = ssm_flops.decode_mixer_bytes(288, row, weights, 18)
    assert nbytes == 2 * 288 * row + 18 * weights
    assert ssm_flops.least_ms(nbytes, 819e9) == pytest.approx(7.48, abs=0.01)


def _ctx(tmp_path, decode_scopes, prefill_scopes=()):
    """A capture made by hand: two decode runs and one prefill run of
    jit_fwd, operations under the given scopes."""
    from xplane_stats import encode

    from benchmark.harness import peaks

    ms = 1_000_000
    ops, meta = [], {}
    for run_start, scopes in ((10 * ms, decode_scopes),
                              (40 * ms, decode_scopes),
                              (70 * ms, prefill_scopes)):
        for j, (scope, dur) in enumerate(scopes):
            name = f"%op.{len(ops)} = bf16[8] fusion(%x), kind=kLoop"
            ops.append((name, run_start + 2 * j * ms, dur * ms))
            meta[name] = {"tf_op": "jit(fwd)/Granite/layer_0/" + scope}
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
    row = 3 * 8448 * 2 + 128 * 64 * 128 * 4
    before = {"slots_total": 16, "slots_used": 16, "decode_runs": 0,
              "state_rows_updated": 0, "state_row_bytes": row,
              "mixer_weight_bytes": 204_472_320}
    after = dict(before, decode_runs=10, state_rows_updated=10 * 288)
    return {"trace_path": path, "sizes": {"n_layer": 20, "ssm_layers": 18},
            "peaks": peaks.peaks_for("TPU v5 lite"),
            "serve": {"before": {"state": before},
                      "at_end": {"state": after}}}


NAMES = ("ssm.mixer_ms.sat", "ssm.mixer_roofline.sat", "ssm.scan_ms.sat",
         "moe.shared_ms.sat")


def test_readers_on_a_hand_made_capture(tmp_path):
    ctx = _ctx(tmp_path,
               [("mamba/ssm.in_proj/dot_general", 4),
                ("mamba/ssm.conv/mul", 0.5),
                ("mamba/ssm.step/while/body/closed_call/mul", 5),
                ("mamba/ssm.gate_norm/norm/mul", 0.5),
                ("mamba/ssm.out_proj/dot_general", 2),
                ("mlp/moe.shared/shared_up/dot_general", 1.5),
                ("mlp/moe/moe.experts/ragged_dot", 7)],
               [("mamba/ssm.scan/while/body/dot_general", 6),
                ("mamba/ssm.in_proj/dot_general", 12)])
    read = {name: manifest.load_reader(name) for name in NAMES}
    # per decode run; the prefill run's operations are not a decode run's
    assert read["ssm.mixer_ms.sat"](ctx) == pytest.approx(12.0)
    assert read["moe.shared_ms.sat"](ctx) == pytest.approx(1.5)
    assert read["ssm.scan_ms.sat"](ctx) == pytest.approx(6.0)
    # 6.125 GB: 7.48 ms at the peak, of 12 ms
    assert read["ssm.mixer_roofline.sat"](ctx) == pytest.approx(
        62.3, abs=0.1)
    phases = ctx["info"]["phases"]
    assert phases["ssm_capture"]["scan_ms_by_bucket"] == {"256": 6.0}
    assert phases["ssm_capture"]["ms_by_scope"]["ssm.step"] == 5.0
    assert phases["ssm_state_rows_per_run"]["state_rows_updated"] == 288


def test_readers_return_nothing_without_names_or_counters(tmp_path):
    """As on the parent, whose program has neither the scopes nor
    stats()["state"]: nothing to read, nothing raised."""
    ctx = _ctx(tmp_path, [("mlp/moe/moe.experts/ragged_dot", 5)])
    ctx["serve"] = {"before": {}, "at_end": {}}
    for name in NAMES:
        assert manifest.load_reader(name)(ctx) is None
