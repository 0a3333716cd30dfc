"""What PR 53 added to the benchmark, by hand on the CPU: the loader finds
benchmark/families/cohere2_moe.py by name and it maps the configuration to
the program's config; the configuration file holds every number of the
catalog's row and cuts depth, experts held and vocabulary alone; the
reference against the program through the family row;
serve-command-a-plus-16k rehearsed at a tiny size through rehearse_run.py
(traced and not); the band's and the triangle's FLOPs and bytes on worked
numbers; the six new readers on a hand-made capture."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import rehearsal
from benchmark.harness import manifest, swa_flops, swa_phases
from benchmark.harness.families import family_of

CELL = "serve-command-a-plus-16k"
KINDS = ["sliding_attention"] * 3 + ["full_attention"]
TINY = {
    "family": "cohere2_moe", "source": "a tiny preset for CPU rehearsals",
    "model_type": "cohere2_moe", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 32, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "hidden_act": "silu", "max_position_embeddings": 128,
    "attention_bias": False, "layer_norm_eps": 1e-05,
    "tie_word_embeddings": True, "layer_types": KINDS,
    "use_parallel_block": True, "use_qk_norm": False,
    "expert_selection_fn": "sigmoid", "norm_topk_prob": True,
    "rotary_pct": 1, "position_embedding_type": "rope_gptj",
    "shared_expert_combination_strategy": "average",
    "first_k_dense_replace": 0, "use_gated_activation": True,
    "num_experts": 4, "first_expert": 2, "published": {"num_experts": 8},
    "num_experts_per_tok": 2, "num_shared_experts": 2,
    "sliding_window": 8, "rope_theta": 10000.0, "logit_scale": 1,
    "compute_dtype": "bfloat16", "param_dtype": "bfloat16", "reduced": []}


def test_loader_finds_the_family_file_by_name():
    fam = family_of(TINY)
    assert (fam.name, fam.engine_model, fam.reference) == (
        "cohere2_moe", "cohere2moe", "cohere2_moe_ref")
    cfg = fam.program_config(TINY, attn_impl="dense", remat=False)
    assert cfg.layer_types == tuple(KINDS)
    assert (cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.head_dim,
            cfg.sliding_window, cfg.d_ff, cfg.n_experts, cfg.first_expert,
            cfg.held_experts, cfg.experts_per_token, cfg.n_shared_experts,
            cfg.max_seq, cfg.rms_eps, cfg.rope_theta) == (
                64, 4, 2, 32, 8, 32, 8, 2, 4, 2, 2, 128, 1e-5, 10000.0)
    assert cfg.parallel_block and cfg.tied_head
    assert cfg.param_dtype == jnp.bfloat16 and not cfg.remat
    sizes = fam.sizes(TINY)
    assert (sizes["n_layer"], sizes["kv_layers"], sizes["window_layers"],
            sizes["window"], sizes["n_head"], sizes["n_kv_head"],
            sizes["head_dim"], sizes["n_experts"], sizes["held_experts"],
            sizes["vocab"], sizes["max_seq"]) == (
                4, 1, 3, 8, 4, 2, 32, 8, 4, 512, 128)
    for other in ({"use_parallel_block": False},
                  {"tie_word_embeddings": False}, {"attention_bias": True},
                  {"position_embedding_type": "rope"},
                  {"shared_expert_combination_strategy": "sum"},
                  {"expert_selection_fn": "softmax"},
                  {"first_k_dense_replace": 1}, {"num_hidden_layers": 5}):
        with pytest.raises(ValueError, match="source's choices"):
            fam.program_config(dict(TINY, **other))


def test_configuration_file_holds_the_catalogs_numbers_and_its_cuts_alone():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "command-a-plus-05-2026")
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == "command-a-plus-05-2026")
    config = manifest.load_json(os.path.join(manifest.ROOT, entry["file"]),
                                "config")
    assert entry["source"] == config["source"] == row["source_url"]
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "layer_types", "num_experts", "vocab_size"]
    for key, value in row["config"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    # the floors: one whole period, >= 8 experts, an eighth of the
    # vocabulary; no width cut
    assert config["num_hidden_layers"] == 4
    assert config["layer_types"] == row["config"]["layer_types"][:4] \
        == KINDS
    assert config["num_experts"] == 16 >= 8
    assert config["published"]["num_experts"] == 128
    assert config["vocab_size"] * 8 == row["config"]["vocab_size"]
    for key in ("intermediate_size", "shared_experts", "router_input",
                "global_layers", "vision_tower", "initializer_range",
                "compute_dtype"):
        assert config["assumed"][key]
    assert set(config["reduced_why"]) == set(config["reduced"])
    cfg = family_of(config).program_config(config)
    assert (cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.head_dim,
            cfg.sliding_window, cfg.d_ff, cfg.experts_per_token,
            cfg.n_experts, cfg.held_experts, cfg.n_shared_experts) == (
                4096, 128, 8, 128, 4096, 4096, 8, 128, 16, 4)
    layer = 2 * 4096 * 128 * (128 + 8) + 3 * 4096 * 16384 + 4096 * 128 \
        + 4096
    params = 4 * (layer + 16 * 3 * 4096 * 4096) + 32768 * 4096 + 4096
    assert params == pytest.approx(4.73e9, rel=2e-3)


def test_reference_equals_the_program_through_the_family_row():
    """... holding a SHARE of the experts (4 of 8, from the third on), as
    the cell does: what the absent ones would add is left out alike."""
    fam = family_of(TINY)
    config = dict(TINY, compute_dtype="float32", param_dtype="float32")
    cfg = fam.program_config(config, attn_impl="dense", remat=False)
    params = fam.init(cfg, jax.random.PRNGKey(3))
    params = jax.tree_util.tree_map(
        lambda w: w if w.ndim == 1 else 4.0 * w, params)
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 512, (2, 21)),
                         jnp.int32)
    from benchmark.reference import cohere2_moe_ref as ref
    from ray_tpu.models.cohere import Cohere2Moe

    got = Cohere2Moe(cfg).apply(params, tokens[:, :-1])
    want = ref.forward(config, params, tokens[:, :-1])
    np.testing.assert_allclose(got, want, atol=1e-4)
    loss = fam.loss(cfg, params, {"tokens": tokens})
    assert abs(float(loss) - float(ref.loss(config, params, tokens))) < 1e-4


# ------------------------------------------------------------- rehearsal

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """rehearsal.build's copy, with a tiny Command A+ cell added beside its
    tiny GPT-2 ones: files and entries only."""
    root = rehearsal.build(str(tmp_path_factory.mktemp("bench")))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    m["configs"].append({"name": "command-a-tiny",
                         "source": TINY["source"],
                         "file": "benchmark/configs/command-a-tiny.json",
                         "reduced": [], "why": "CPU rehearsal"})
    m["workloads"].append({"name": "tiny-command-a-sat",
                           "config": "command-a-tiny",
                           "traffic": "tiny-closed", "chips": 1,
                           "why": "CPU rehearsal"})
    for group in ("end_to_end", "per_layer"):
        for metric in m[group]:
            if CELL in metric.get("workloads", ()):
                metric["workloads"].append("tiny-command-a-sat")
    rehearsal._write(path, m)
    rehearsal._write(os.path.join(
        root, "benchmark/configs/command-a-tiny.json"), TINY)
    rehearsal._write(
        os.path.join(root, "benchmark/cells/tiny-command-a-sat.json"),
        {"engine": {"page_size": 4, "num_pages": 128, "max_batch": 4,
                    "max_context": 64}})
    return root


@pytest.mark.parametrize("trace", [0, 1])
def test_serving_cell_rehearsed_on_cpu(root, trace):
    out = rehearsal.run_cell(root, "tiny-command-a-sat", trace=trace)
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
    rows = info["detail"]["phases"]["swa_rows_per_run"]
    assert rows["kv_row_bytes"] == 2 * 64 * 2       # K and V, bf16
    assert (rows["window"], rows["window_layers"]) == (8, 3)
    # 4 rows x (16 pages of the full layer + 3 x a ring of 2) of 4 rows,
    # over 4 x 16 x 4 layers
    assert line["metrics"]["swa.kv_held_share.sat"]["value"] == pytest.approx(
        100 * (16 + 3 * 2) / (4 * 16))
    assert "engine.step_ms.sat" in line["metrics"]
    assert "swa.prefill_ms.sat" not in line["metrics"]


# ----------------------------------------------- readers, worked numbers

def test_flops_and_bytes_on_worked_numbers():
    """By hand.  A window of 4 over 6 rows: the rows meet 1, 2, 3, 4, 4, 4
    keys = 18 pairs; over 3 rows 1 + 2 + 3 = 6.  At the cell's 16,384
    bucket a window layer's band is 4,096 x 4,097 / 2 + 12,288 x 4,096 =
    58,722,304 pairs: 4 x 128 x 128 x that = 3.85 TFLOP a layer (19.5 ms
    at 197 TFLOP/s) against a triangle of 134,225,920 pairs, 8.80 TFLOP:
    the band is 44% of it.  Its bytes, q and the output at 128 heads, k
    and v at 8: 16,384 x 128 x (256 + 16) x 2 = 1.14 GB (1.39 ms at 819
    GB/s): compute bounds it.  The two groups hold 16,384 + 3 x 4,096 of a
    window-blind 4 x 16,384 positions a row: 43.75%."""
    assert swa_flops.band_pairs(6, 4) == 18
    assert swa_flops.band_pairs(3, 4) == 6
    assert swa_flops.band_pairs(16384, 4096) == 58_722_304
    assert swa_flops.band_pairs(16384, 16384) == 134_225_920
    band = swa_flops.band_flops(16384, 1, 128, 128, 4096)
    tri = swa_flops.triangle_flops(16384, 1, 128, 128)
    assert band == 4 * 128 * 128 * 58_722_304 == pytest.approx(3.848e12,
                                                              rel=1e-3)
    assert tri == pytest.approx(8.797e12, rel=1e-3)
    assert band / tri == pytest.approx(0.4375, abs=1e-3)
    nbytes = swa_flops.prefill_bytes(16384, 1, 128, 8, 128)
    assert nbytes == 16384 * 128 * (256 + 16) * 2 == 1_140_850_688
    assert swa_flops.least_ms(band, nbytes, 197e12, 819e9) \
        == pytest.approx(19.53, rel=1e-3)
    assert swa_flops.attend_bytes(16 * 4096 * 3, 4096) == 805_306_368
    assert swa_flops.held_share(1, 3, 16384, 4096) == 0.4375


WINDOW = "attn/attn.core/attn.window/"
FULL = "attn/attn.core/attn.full/"
FLASH = "%flash_fwd.{n} = bf16[1,8192,16384] custom-call(%q), " \
    "custom_call_target=\"tpu_custom_call\""
PAGED = "%paged_decode.{n} = bf16[16,16,1024] custom-call(%q), " \
    "custom_call_target=\"tpu_custom_call\""


def _ctx(tmp_path, decode_scopes, prefill_scopes=(), window_ends=100):
    """A capture made by hand: two decode runs and one prefill run (of the
    8,192 bucket) of jit_fwd, operations under the given scopes (durations
    in ms; a third entry is the whole instruction where its name
    matters)."""
    from xplane_stats import encode

    from benchmark.harness import peaks

    ms = 1_000_000
    ops, meta = [], {}
    for run_start, scopes in ((10 * ms, decode_scopes),
                              (40 * ms, decode_scopes),
                              (70 * ms, prefill_scopes)):
        at = run_start
        for scope, dur, *code in scopes:
            name = f"%op.{len(ops)} = bf16[8] fusion(%x), kind=kLoop" \
                if not code else code[0].format(n=len(ops))
            ops.append((name, at, int(dur * ms)))
            at += int(dur * ms)
            if scope:
                meta[name] = {"tf_op": "jit(fwd)/Cohere2Moe/layer_2/" + scope}
    path = str(tmp_path / "t.xplane.pb")
    with open(path, "wb") as f:
        f.write(encode([
            ("/device:TPU:0", {
                "XLA Modules": [("jit_fwd(1)", 10 * ms, 25 * ms),
                                ("jit_fwd(1)", 40 * ms, 25 * ms),
                                ("jit_fwd(2)", 70 * ms, 25 * ms)],
                "XLA Ops": ops}),
            ("/host:CPU", {"engine": [
                ("bench_window", 5 * ms, (window_ends - 5) * ms),
                ("llm.decode", 9 * ms, 28 * ms),
                ("llm.decode", 39 * ms, 28 * ms),
                ("llm.prefill", 69 * ms, 28 * ms, {"bucket": 8192}),
                ("llm.step", 9 * ms, 29 * ms),
                ("llm.step", 39 * ms, 29 * ms)]})], meta))
    before = {"decode_runs": 0, "kv_rows_read": 0, "kv_rows_held": 0,
              "kv_row_bytes": 4096, "window": 4096, "window_layers": 3,
              "window_rows_read": 0, "window_rows_held": 0,
              "window_positions_dropped": 0}
    # ten runs of 16 rows at 6,000 positions: 375 pages of the full layer,
    # a ring of 256 in each of 3 window layers
    after = dict(before, decode_runs=10,
                 kv_rows_read=10 * 16 * 16 * (375 + 3 * 256),
                 kv_rows_held=10 * 16 * 16 * (1024 + 3 * 256),
                 window_rows_read=10 * 16 * 16 * 3 * 256,
                 window_rows_held=10 * 16 * 16 * 3 * 256,
                 window_positions_dropped=10 * 16 * 16 * 3 * (375 - 256))

    class Cell:
        config = {"layer_types": KINDS}

    return {"trace_path": path, "cell": Cell,
            "sizes": {"n_layer": 4, "kv_layers": 1, "window_layers": 3,
                      "window": 4096, "n_head": 128, "n_kv_head": 8,
                      "head_dim": 128},
            "peaks": peaks.peaks_for("TPU v5 lite"),
            "serve": {"before": {"attention": before},
                      "at_end": {"attention": after}}}


NAMES = ("swa.prefill_ms.sat", "swa.prefill_roofline.sat",
         "swa.full_prefill_roofline.sat", "swa.attend_ms.sat",
         "swa.attend_roofline.sat", "swa.kv_held_share.sat")


@pytest.mark.parametrize("kernels_carry_scopes", [True, False])
def test_readers_on_a_hand_made_capture(tmp_path, kernels_carry_scopes):
    """... whether a kernel's event carries its scope path or none (then
    the i-th call of a run is layer i's: three window layers, then the
    full one)."""
    def at(scope):
        return scope if kernels_carry_scopes else ""

    decode = [("attn/attn.qkv/wq/dot_general", 0.3)]
    for scope in (WINDOW, WINDOW, WINDOW, FULL):
        decode += [(scope + "kv.store/scatter", 0.05),
                   (at(scope + "kv.attend"), 0.5 if scope == WINDOW
                    else 0.9, PAGED),
                   (scope + "kv.attend/transpose", 0.1)]
    prefill = [("attn/attn.qkv/wq/dot_general", 1)]
    for scope in (WINDOW, WINDOW, WINDOW, FULL):
        prefill += [(scope + "kv.store/scatter", 0.5),
                    (scope + "transpose", 0.25),
                    (at(scope + "flash_fwd"), 2.75 if scope == WINDOW
                     else 5.75, FLASH)]
    ctx = _ctx(tmp_path, decode + [("mlp/moe/moe.experts/ragged", 2)],
               prefill + [("mlp/moe/moe.experts/ragged", 3)])
    read = {name: manifest.load_reader(name) for name in NAMES}
    # per prefill run: three window layers' kernel and what surrounds it,
    # the store apart
    assert read["swa.prefill_ms.sat"](ctx) == pytest.approx(9.0)
    least = swa_flops.least_ms(
        swa_flops.band_flops(8192, 3, 128, 128, 4096),
        swa_flops.prefill_bytes(8192, 3, 128, 8, 128), 197e12, 819e9)
    assert read["swa.prefill_roofline.sat"](ctx) == pytest.approx(
        100 * least / 9.0, rel=1e-6)
    least = swa_flops.least_ms(
        swa_flops.triangle_flops(8192, 1, 128, 128),
        swa_flops.prefill_bytes(8192, 1, 128, 8, 128), 197e12, 819e9)
    assert read["swa.full_prefill_roofline.sat"](ctx) == pytest.approx(
        100 * least / 6.0, rel=1e-6)
    # per decode run: the window layers' kernel and the transposes beside
    assert read["swa.attend_ms.sat"](ctx) == pytest.approx(1.8)
    # 16 rows x 3 layers x 4,096 positions x 4,096 B at 819 GB/s
    assert read["swa.attend_roofline.sat"](ctx) == pytest.approx(
        100 * (16 * 3 * 4096 * 4096 / 819e9 * 1e3) / 1.8, rel=1e-6)
    assert read["swa.kv_held_share.sat"](ctx) == pytest.approx(43.75)
    cap = ctx["info"]["phases"]["swa_capture"]
    assert (cap["decode_runs"], cap["prefill_runs"]) == (2, 1)
    assert cap["decode_ms"]["attn.full/kv.attend"] == pytest.approx(1.0)
    assert cap["decode_ms"]["attn.window/kv.store"] == pytest.approx(0.15)
    assert cap["full_ms_by_bucket"] == {"8192": [pytest.approx(6.0)]}
    rows = ctx["info"]["phases"]["swa_rows_per_run"]
    assert rows["window_positions_dropped"] == 16 * 16 * 3 * (375 - 256)


def test_a_run_the_captures_edge_cuts_is_left_out(tmp_path):
    """A part of a run's time against all of its work would read over the
    roofline: the window ends inside the prefill run, which then gives
    nothing; the decode runs before it are read as ever."""
    scopes = [(WINDOW + "kv.attend", 0.5, PAGED)]
    ctx = _ctx(tmp_path, scopes, [(WINDOW + "flash_fwd", 2.0, FLASH)],
               window_ends=80)
    assert manifest.load_reader("swa.prefill_ms.sat")(ctx) is None
    assert manifest.load_reader("swa.prefill_roofline.sat")(ctx) is None
    assert manifest.load_reader("swa.attend_ms.sat")(ctx) == \
        pytest.approx(0.5)
    whole = _ctx(tmp_path, scopes, [(WINDOW + "flash_fwd", 2.0, FLASH)])
    assert manifest.load_reader("swa.prefill_ms.sat")(whole) == \
        pytest.approx(2.0)


def test_readers_return_nothing_without_names_or_counters(tmp_path):
    """As on a program that has neither the scopes nor the counters, or a
    family file without the sizes (the parent's, any other family's):
    nothing to read, nothing raised."""
    ctx = _ctx(tmp_path, [("mlp/w_up/dot_general", 5)])
    ctx["cell"].config = {"layer_types": []}
    ctx["serve"] = {"before": {"attention": {"decode_runs": 0}},
                    "at_end": {"attention": {"decode_runs": 4}}}
    for name in NAMES:
        assert manifest.load_reader(name)(ctx) is None
    ctx["sizes"] = {"n_layer": 8}
    ctx.pop("_swa_capture")
    for name in NAMES:
        assert manifest.load_reader(name)(ctx) is None
    assert swa_phases.file_run([], {}, KINDS) == {}
