"""What PR 48 added to the benchmark, by hand on the CPU: the loader finds
benchmark/families/olmo_hybrid.py by name and it maps the configuration to
the program's config; the configuration file holds every number of the
catalog's row and cuts the depth alone; the reference against the program
through the family row; serve-olmo-hybrid-7b-4k rehearsed at a tiny size
through rehearse_run.py (traced and not); the delta rule's and the K/V
prefill's FLOPs and bytes on worked numbers; the six new readers on a
hand-made capture."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import rehearsal
from benchmark.harness import gdn_flops, manifest
from benchmark.harness.families import family_of

CELL = "serve-olmo-hybrid-7b-4k"
KINDS = ["linear_attention"] * 3 + ["full_attention"]
TINY = {
    "family": "olmo_hybrid", "source": "a tiny preset for CPU rehearsals",
    "model_type": "olmo_hybrid", "vocab_size": 512, "hidden_size": 60,
    "intermediate_size": 96, "num_hidden_layers": 8,
    "num_attention_heads": 6, "num_key_value_heads": 6,
    "hidden_act": "silu", "max_position_embeddings": 128,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "layer_types": KINDS * 2,
    "linear_num_key_heads": 6, "linear_num_value_heads": 6,
    "linear_key_head_dim": 12, "linear_value_head_dim": 24,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}, "l2_norm_eps": 1e-06,
    "compute_dtype": "bfloat16", "param_dtype": "bfloat16", "reduced": []}


def test_loader_finds_the_family_file_by_name():
    fam = family_of(TINY)
    assert (fam.name, fam.engine_model, fam.reference) == (
        "olmo_hybrid", "olmohybrid", "olmo_hybrid_ref")
    cfg = fam.program_config(TINY, attn_impl="dense", remat=False)
    assert cfg.layer_types == tuple(KINDS * 2)
    assert (cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.gdn_heads,
            cfg.gdn_key_dim, cfg.gdn_value_dim, cfg.gdn_conv, cfg.d_ff,
            cfg.max_seq, cfg.rms_eps) == (60, 6, 6, 6, 12, 24, 4, 96, 128,
                                          1e-6)
    assert cfg.norm_output and cfg.experts is None
    assert cfg.n_dense_layers == 8
    assert cfg.param_dtype == jnp.bfloat16 and not cfg.remat
    sizes = fam.sizes(TINY)
    assert (sizes["n_layer"], sizes["kv_layers"], sizes["gdn_layers"],
            sizes["gdn_heads"], sizes["gdn_key_dim"],
            sizes["gdn_value_dim"], sizes["n_head"], sizes["head_dim"],
            sizes["vocab"], sizes["max_seq"]) == (
                8, 2, 6, 6, 12, 24, 6, 10, 512, 128)
    for other in ({"linear_allow_neg_eigval": False},
                  {"tie_word_embeddings": True}, {"attention_bias": True},
                  {"rope_parameters": {"rope_theta": 10000.0}},
                  {"linear_num_key_heads": 3}, {"l2_norm_eps": 0.0},
                  {"num_hidden_layers": 7}):
        with pytest.raises(ValueError, match="source's choices"):
            fam.program_config(dict(TINY, **other))


def test_configuration_file_holds_the_catalogs_numbers_and_cuts_depth_alone():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Olmo-Hybrid-7B")
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == "olmo-hybrid-7b")
    config = manifest.load_json(os.path.join(manifest.ROOT, entry["file"]),
                                "config")
    assert entry["source"] == config["source"] == row["source_url"]
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "layer_types"]
    for key, value in row["config"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert config["num_hidden_layers"] == 16
    assert config["layer_types"] == row["config"]["layer_types"][:16] \
        == KINDS * 4
    for key in ("block_norm", "qk_norm", "nope", "gdn_gate", "gdn_decay",
                "initializer_range", "compute_dtype"):
        assert config["assumed"][key]
    cfg = family_of(config).program_config(config)
    params = cfg.vocab_size * cfg.d_model * 2 + 12 * (
        cfg.mixer_params() + 3 * cfg.d_model * cfg.d_ff) + 4 * (
        cfg.attention_params() + 3 * cfg.d_model * cfg.d_ff)
    assert params == pytest.approx(4.10e9, rel=2e-3)


def test_reference_equals_the_program_through_the_family_row():
    fam = family_of(TINY)
    config = dict(TINY, compute_dtype="float32", param_dtype="float32")
    cfg = fam.program_config(config, attn_impl="dense", remat=False)
    params = fam.init(cfg, jax.random.PRNGKey(3))
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 512, (2, 21)),
                         jnp.int32)
    from benchmark.reference import olmo_hybrid_ref as ref
    from ray_tpu.models.olmo_hybrid import OlmoHybrid

    got = OlmoHybrid(cfg).apply(params, tokens[:, :-1])
    want = ref.forward(config, params, tokens[:, :-1])
    np.testing.assert_allclose(got, want, atol=1e-4)
    loss = fam.loss(cfg, params, {"tokens": tokens})
    assert abs(float(loss) - float(ref.loss(config, params, tokens))) < 1e-4


# ------------------------------------------------------------- rehearsal

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """rehearsal.build's copy, with a tiny Olmo-Hybrid cell added beside
    its tiny GPT-2 ones: files and entries only."""
    root = rehearsal.build(str(tmp_path_factory.mktemp("bench")))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    m["configs"].append({"name": "olmo-hybrid-tiny",
                         "source": TINY["source"],
                         "file": "benchmark/configs/olmo-hybrid-tiny.json",
                         "reduced": [], "why": "CPU rehearsal"})
    m["workloads"].append({"name": "tiny-olmo-hybrid-sat",
                           "config": "olmo-hybrid-tiny",
                           "traffic": "tiny-closed", "chips": 1,
                           "why": "CPU rehearsal"})
    for group in ("end_to_end", "per_layer"):
        for metric in m[group]:
            if CELL in metric.get("workloads", ()):
                metric["workloads"].append("tiny-olmo-hybrid-sat")
    rehearsal._write(path, m)
    rehearsal._write(os.path.join(
        root, "benchmark/configs/olmo-hybrid-tiny.json"), TINY)
    rehearsal._write(
        os.path.join(root, "benchmark/cells/tiny-olmo-hybrid-sat.json"),
        {"engine": {"page_size": 4, "num_pages": 128, "max_batch": 4,
                    "max_context": 64}})
    return root


@pytest.mark.parametrize("trace", [0, 1])
def test_serving_cell_rehearsed_on_cpu(root, trace):
    out = rehearsal.run_cell(root, "tiny-olmo-hybrid-sat", trace=trace)
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
    rows = info["detail"]["phases"]["attend_rows_per_run"]
    assert rows["kv_row_bytes"] == 2 * 60 * 2       # K and V, bf16
    assert "engine.step_ms.sat" in line["metrics"]
    assert "gdn.mixer_ms.sat" not in line["metrics"]
    assert "attn.prefill_ms.sat" not in line["metrics"]


# ----------------------------------------------- readers, worked numbers

def test_flops_and_bytes_on_worked_numbers():
    """A decode run of the cell, 16 rows x 12 layers = 192 rows: the
    recurrence moves 192 x 2,211,840 B x 2 = 0.849 GB (1.037 ms at 819
    GB/s).  A token of a prefill costs a head 7 x 96 x 192 = 129,024
    FLOPs and 2 x (192 + 384) + 4 = 1,156 bytes: at the 4,096 bucket and
    12 layers 190.2 GFLOP (0.966 ms at 197 TFLOP/s) and 1.705 GB (2.081
    ms): the memory bounds it.  The causal triangle of 30 heads of 128 at
    4,096 rows is 128.8 GFLOP a layer, 515.4 GFLOP over 4 layers (2.616 ms
    at the peak)."""
    assert gdn_flops.step_bytes(1, 30, 96, 192) == 2 * 2_211_840
    assert gdn_flops.least_ms(gdn_flops.step_bytes(192, 30, 96, 192),
                              819e9) == pytest.approx(1.0371, rel=1e-4)
    assert gdn_flops.scan_flops(1, 1, 1, 96, 192) == 129_024
    assert gdn_flops.scan_bytes(1, 1, 1, 96, 192) == 1_156
    f = gdn_flops.scan_flops(4096, 12, 30, 96, 192)
    b = gdn_flops.scan_bytes(4096, 12, 30, 96, 192)
    assert f == pytest.approx(190.2e9, rel=1e-3)
    assert b == pytest.approx(1.7046e9, rel=1e-3)
    assert f / 197e12 < b / 819e9
    a = gdn_flops.prefill_attend_flops(4096, 4, 30, 128)
    assert a == 2 * 4096 * 4096 * 128 * 30 * 4 == pytest.approx(
        515.4e9, rel=1e-3)
    assert a / 197e12 > gdn_flops.prefill_attend_bytes(4096, 4, 30, 128) \
        / 819e9


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
                meta[name] = {"tf_op": "jit(fwd)/OlmoHybrid/layer_2/" + scope}
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
              "state_row_bytes": 2_280_960,
              "mixer_weight_bytes": 88_750_080 * 2, "slots_used": 16,
              "slots_total": 16}
    # 12 of the 16 rows ran in each of the 12 layers
    after = dict(before, decode_runs=10, state_rows_updated=10 * 144)
    return {"trace_path": path,
            "sizes": {"n_layer": 16, "kv_layers": 4, "gdn_layers": 12,
                      "gdn_heads": 30, "gdn_key_dim": 96,
                      "gdn_value_dim": 192, "n_head": 30, "head_dim": 128},
            "peaks": peaks.peaks_for("TPU v5 lite"),
            "serve": {"before": {"state": before},
                      "at_end": {"state": after}}}


NAMES = ("gdn.mixer_ms.sat", "gdn.step_roofline.sat", "gdn.scan_ms.sat",
         "gdn.scan_roofline.sat", "attn.prefill_ms.sat",
         "attn.prefill_roofline.sat")
KERNEL = "%kda_step.{n} = f32[16,15,384] custom-call(%x), " \
    "custom_call_target=\"tpu_custom_call\""
FLASH = "%flash_fwd.{n} = bf16[1,256,3840] custom-call(%q), " \
    "custom_call_target=\"tpu_custom_call\""


def test_readers_on_a_hand_made_capture(tmp_path):
    ctx = _ctx(tmp_path,
               [("gdn/gdn.proj/wq/dot_general", 0.3),
                ("gdn/gdn.conv/scatter", 0.1),
                ("gdn/gdn.gate/exp", 0.05),
                ("gdn/gdn.step/transpose", 0.5),
                ("", 1.5, KERNEL),
                ("gdn/gdn.out_norm/o_norm/mul", 0.05),
                ("gdn/gdn.out_proj/wo/dot_general", 0.5),
                ("attn/attn.core/kv.attend/paged", 0.7),
                ("attn/attn.out/wo/dot_general", 0.7),
                ("mlp/w_up/dot_general", 2)],
               [("gdn/gdn.proj/wq/dot_general", 1),
                ("gdn/gdn.scan/triangular_solve", 3),
                ("gdn/gdn.scan/while/body/dot_general", 1),
                ("attn/attn.core/kv.store/scatter", 0.5),
                ("attn/attn.core/transpose", 0.25),
                ("", 0.75, FLASH),
                ("mlp/w_up/dot_general", 10)])
    read = {name: manifest.load_reader(name) for name in NAMES}
    # per decode run: the gdn scopes and the kernel, not the attention
    # layers' or the FFN's
    assert read["gdn.mixer_ms.sat"](ctx) == pytest.approx(3.0)
    # 144 rows x 2,211,840 B x 2 at 819 GB/s = 0.7778 ms over the 2.0 ms
    # under gdn.step (the kernel and the transposes before it)
    assert read["gdn.step_roofline.sat"](ctx) == pytest.approx(
        100 * 0.77778 / 2.0, rel=1e-4)
    assert read["gdn.scan_ms.sat"](ctx) == pytest.approx(4.0)
    least = gdn_flops.scan_bytes(256, 12, 30, 96, 192) / 819e9
    assert read["gdn.scan_roofline.sat"](ctx) == pytest.approx(
        100 * least / 4e-3, rel=1e-6)
    # attn.core less the store
    assert read["attn.prefill_ms.sat"](ctx) == pytest.approx(1.0)
    shape = (256, 4, 30, 128)
    least = max(gdn_flops.prefill_attend_flops(*shape) / 197e12,
                gdn_flops.prefill_attend_bytes(*shape) / 819e9)
    assert read["attn.prefill_roofline.sat"](ctx) == pytest.approx(
        100 * least / 1e-3, rel=1e-6)
    cap = ctx["info"]["phases"]["gdn_capture"]
    assert (cap["decode_runs"], cap["prefill_runs"]) == (2, 1)
    assert cap["ms_by_scope"]["gdn.step"] == pytest.approx(2.0)
    assert cap["scan_ms_by_bucket"] == {"256": [pytest.approx(4.0)]}
    assert ctx["info"]["phases"]["gdn_step_roofline"]["rows_running"] == 144


def test_readers_return_nothing_without_names_or_counters(tmp_path):
    """As on a program that has neither the scopes nor the counters, or a
    family file without the sizes (the parent's, any other family's):
    nothing to read, nothing raised."""
    ctx = _ctx(tmp_path, [("mlp/w_up/dot_general", 5)])
    for name in NAMES:
        assert manifest.load_reader(name)(ctx) is None
    ctx = _ctx(tmp_path, [("gdn/gdn.step/gather", 1.0)])
    ctx["serve"] = {"before": {}, "at_end": {}}
    assert manifest.load_reader("gdn.step_roofline.sat")(ctx) is None
    assert manifest.load_reader("gdn.mixer_ms.sat")(ctx) == \
        pytest.approx(1.0)
    ctx["sizes"] = {"n_layer": 8}
    ctx.pop("_gdn_capture")
    for name in NAMES:
        assert manifest.load_reader(name)(ctx) is None
