"""The main path's kernels and steps, compiled for the real chip at real
widths — a compile by the TPU's own compiler for a DESCRIBED v5e:2x2
topology, not a run (on-chip-measurement guide, section 2).  They guard
every later PR at no chip time: a kernel the chip's compiler refuses
(tiling, fast-memory budget, HBM fit, partitioning) fails here.

Rules this file keeps: the topology is described inside a module-scoped
fixture that skips where it cannot be (never at import, never autouse,
never in conftest.py); everything compiles in the test's own process
(only one process may load the TPU library); all such tests live in this
one file; the persistent compile cache is off around them.  The code
under test asks ``jax.default_backend()`` and would take its interpret
branch here, so the tests steer it (``interpret=False``, or patching the
name the model imports) — not an option of the program.
"""

import contextlib
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

GPT2_124M = dict(n_layer=12, n_head=12, d_model=768, d_ff=3072,
                 vocab_size=50257, max_seq=1024)
HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # Such a compile would be written to the persistent cache but can
    # never be read back without a chip: keep the cache out of it.
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh_2x2(topo):
    return Mesh(np.array(topo.devices).reshape(2, 2), ("fsdp", "tensor"))


@pytest.fixture
def compiled_kernel(monkeypatch):
    """The model imports ``ray_tpu.ops.flash_attention`` (GPT-2's
    training block ``flash_attention_qkv``) at call time: hand it the
    compiled kernel, as the backend ``tpu`` would."""
    import ray_tpu.ops

    for name in ("flash_attention", "flash_attention_qkv"):
        monkeypatch.setattr(ray_tpu.ops, name, functools.partial(
            getattr(ray_tpu.ops, name), interpret=False))


def _on(tree, sharding):
    """Shapes of ``tree`` placed by ``sharding`` (one, or a tree)."""
    if not isinstance(sharding, jax.sharding.Sharding):
        return jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=sh),
            tree, sharding)
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                       sharding=sharding), tree)


def _served(tokens_shape, sharding):
    """What the engine passes its forward beside the positional arguments
    (llm/engine.py ``_prefill_annotated``): for a prefill the position of
    the row it serves, for a decode step nothing."""
    if tokens_shape[1] == 1:
        return {}
    return {"last": jax.ShapeDtypeStruct(tokens_shape[:1], jnp.int32,
                                         sharding=sharding)}


def _logits_shape(program):
    """The first output's shape, of a lowered or a compiled program."""
    return tuple(jax.tree_util.tree_leaves(program.out_info)[0].shape)


def _device_bytes(compiled) -> float:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _scan_loop_dots(text, scope):
    """One count a ``while`` of the compiled text whose body works under
    ``scope`` (``gdn.scan``, ``kda.scan``): the matmuls one turn holds,
    those inside the fusions it calls too."""
    comps = {m.group(1): m.group(2) for m in re.finditer(
        r"^(?:ENTRY )?%?([\w.\-]+) \([^\n]*\) -> [^\n]*\{\n(.*?)^\}", text,
        re.M | re.S)}

    def dots(name):
        body = comps.get(name, "")
        return len(re.findall(r" (?:convolution|dot)\(", body)) + sum(
            dots(callee) for callee in re.findall(r"calls=%?([\w.\-]+)", body))

    return [dots(body) for body in re.findall(
        r" while\([^\n]*?body=%?([\w.\-]+)", text)
        if scope in comps.get(body, "")]


def _assert_scan_is_matmuls(text, scope, layers):
    """The chunked delta-rule scan of a compiled program: no triangular
    solve (nor its expanded loop: the ONE loop a mixer layer is the pass
    over the chunks), and a turn of that pass holds the state's two
    matmuls."""
    assert "triangular" not in text
    loops = _scan_loop_dots(text, scope)
    assert len(loops) == layers and all(n <= 2 for n in loops), loops


KERNEL_NAMES = ("flash_fwd", "flash_dq", "flash_dkv")


def _kernel_calls(text):
    """How many instructions of the compiled text call each flash kernel
    (the benchmark's readers file device time by these names)."""
    return {name: len(re.findall(rf"%{name}(?:\.\d+)? = ", text))
            for name in KERNEL_NAMES}


# The last three are the benchmark cells' own calls (32 rows a chip, the
# four-chip cell's shard) and a family with heads of 128: one 1024 block
# a head, walked in sub-blocks.
@pytest.mark.parametrize("b,t,h,d,block", [
    (16, 1024, 12, 64, 1024), (1, 8192, 12, 64, 256),
    (32, 1024, 12, 64, 1024), (16, 1024, 10, 64, 1024),
    (8, 1024, 16, 128, 1024)],
    ids=["gpt2_b16_t1024_blk1024", "b1_t8192_blk256",
         "cell_b32_t1024_blk1024", "fsdp2x2_shard_b16_h10_blk1024",
         "d128_b8_t1024_blk1024"])
def test_flash_attention_fwd_bwd_compiles(one_chip, b, t, h, d, block):
    from ray_tpu.ops.flash_attention import flash_attention

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, block_q=block,
                              block_k=block, interpret=False)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    x = jax.ShapeDtypeStruct((b, t, h, d), jnp.bfloat16,
                             sharding=one_chip)
    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))
                       ).lower(x, x, x).compile()
    # Forward, dq and dkv kernels are all in the program, by name.
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3
    assert _kernel_calls(text) == dict.fromkeys(KERNEL_NAMES, 1)


@pytest.mark.parametrize("b,h,d", [(32, 12, 64), (8, 16, 128)],
                         ids=["cell_b32_two_heads_a_program",
                              "d128_one_head_a_program"])
def test_flash_attention_qkv_compiles_with_no_transpose(one_chip, b, h, d):
    """The fused entry at the cell's shape: q, k, v are three column
    ranges of ONE [B, T, 3*H*D] operand of each kernel, and nothing the
    size of an activation is copied or transposed around them; the
    cotangent is the three joined by one fusion."""
    from ray_tpu.ops import flash_attention_qkv

    def loss(qkv, w):
        out = flash_attention_qkv(qkv, h, causal=True, block_q=1024,
                                  block_k=1024, interpret=False)
        return jnp.sum((out @ w).astype(jnp.float32) ** 2)

    qkv = jax.ShapeDtypeStruct((b, 1024, 3 * h * d), jnp.bfloat16,
                               sharding=one_chip)
    w = jax.ShapeDtypeStruct((h * d, h * d), jnp.bfloat16,
                             sharding=one_chip)
    text = jax.jit(jax.value_and_grad(loss)).lower(qkv, w).compile() \
        .as_text()
    assert _kernel_calls(text) == dict.fromkeys(KERNEL_NAMES, 1)
    whole = f"bf16[{b},1024,{3 * h * d}]"
    for name in KERNEL_NAMES:
        call, = re.findall(rf"%{name}(?:\.\d+)? = .*", text)
        assert call.count(whole) == 3, call[:300]
    assert not re.findall(r"= bf16\[[\d,]+\]\S* (?:copy|transpose)\(", text)


def _engine_args(cfg, one_chip, tokens_shape, num_pages=2048,
                 page_size=16):
    from ray_tpu.llm.kv_cache import init_cache, pages_for
    from ray_tpu.models.gpt2 import gpt2_init

    params = jax.eval_shape(
        lambda: gpt2_init(cfg, jax.random.PRNGKey(0)))
    kv = jax.eval_shape(
        lambda: init_cache(cfg.n_layer, num_pages, page_size, cfg.n_head,
                           cfg.d_model // cfg.n_head, cfg.dtype))
    b = tokens_shape[0]
    ints = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                             sharding=one_chip)
    return (_on(params, one_chip), ints(tokens_shape),
            _on(kv["k_pages"], one_chip), _on(kv["v_pages"], one_chip),
            ints((b, pages_for(cfg.max_seq, page_size))),
            ints(tokens_shape))


ENGINE_WIDTHS = {"124m": GPT2_124M,
                 "large": dict(GPT2_124M, n_layer=36, n_head=20,
                               d_model=1280, d_ff=5120)}


@pytest.fixture(scope="module")
def engine_program(one_chip):
    """(compiled jit_forward, the shape of one pool array) by size:
    one compile for all the tests of a size."""
    from ray_tpu.llm.engine import jit_forward
    from ray_tpu.models.gpt2 import GPT2, GPT2Config

    @functools.lru_cache(maxsize=None)
    def program(widths, tokens_shape, num_pages, kernel=False):
        cfg = GPT2Config(**ENGINE_WIDTHS[widths], attn_impl="dense",
                         remat=False)
        args = _engine_args(cfg, one_chip, tokens_shape,
                            num_pages=num_pages)
        with _as_on_tpu(kernel):
            return jit_forward(GPT2(cfg)).lower(
                *args, **_served(tokens_shape, one_chip)).compile(), args[2]

    return program


@contextlib.contextmanager
def _as_on_tpu(kernel=True):
    """The cached attention dispatches as the backend ``tpu`` would (a
    decode step takes the compiled paged-decode kernel, a prefill of 256
    rows or more the compiled flash kernel among its own rows); with
    ``kernel=False`` it is left as this backend has it."""
    import ray_tpu.models.attention as attention
    import ray_tpu.ops
    from ray_tpu.ops import paged_attention

    with pytest.MonkeyPatch.context() as patch:
        if kernel:
            patch.setattr(attention, "_decode_kernel",
                          paged_attention.supported)
            patch.setattr(paged_attention, "paged_decode",
                          functools.partial(paged_attention.paged_decode,
                                            interpret=False))
            patch.setattr(
                attention, "_prefill_impl", lambda t: "flash"
                if t >= attention._FLASH_FROM else "dense")
            patch.setattr(ray_tpu.ops, "flash_attention", functools.partial(
                ray_tpu.ops.flash_attention, interpret=False))
        yield


@pytest.mark.parametrize("kind,tokens_shape", [("decode", (8, 1)),
                                               ("prefill", (1, 512))])
def test_engine_forward_compiles_at_124m(engine_program, kind,
                                         tokens_shape):
    """The engine's own jitted forward (llm/engine.py jit_forward) over a
    2048-page KV pool: the decode step [max_batch, 1] and one prefill
    bucket, which returns the logits of the ONE position it serves."""
    compiled, _ = engine_program("124m", tokens_shape, 2048)
    assert _device_bytes(compiled) < HBM_BYTES
    assert _logits_shape(compiled) == (tokens_shape[0], 1,
                                       GPT2_124M["vocab_size"])


_POOL_PASS = re.compile(r"^\s*(?:ROOT )?%?[\w.-]+ = \w+\[([\d,]+)\]\S* "
                        r"(copy|copy-done|slice|dynamic-update-slice)\(")


@pytest.mark.parametrize("widths,tokens_shape,num_pages", [
    ("124m", (8, 1), 2048), ("124m", (1, 512), 2048),
    ("large", (16, 1), 1024)],
    ids=["124m_decode", "124m_prefill", "large_decode_cell"])
def test_engine_forward_updates_pool_in_place(engine_program, widths,
                                              tokens_shape, num_pages):
    """No pass over the pool but the scatter of the new rows and the
    gather of the running sequences' pages (the last case at the sizes
    of serve-gpt2-large-sat).  With the heads as their own 64-wide
    minor dimension the TPU tiles the pool page-minor, and every layer
    paid a slice, transposes (`copy`) and a dynamic-update-slice of
    one layer of the pool, K and V: 252 such copies and 5.34 GB of
    temporaries at the cell's sizes, against none and 0.36 GB."""
    compiled, pool = engine_program(widths, tokens_shape, num_pages)
    sizes = (pool.size, pool.size // pool.shape[0])     # pool, layer
    passes = []
    for line in compiled.as_text().splitlines():
        m = _POOL_PASS.match(line)
        if m and np.prod([int(d) for d in m.group(1).split(",")]) in sizes:
            passes.append(line.strip()[:120])
    assert not passes, (len(passes), passes[:4])
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 0.5 * pool.size * pool.dtype.itemsize


def _assert_attends_in_place(compiled, pool, n_layer, temp_bytes):
    """A decode program that attends through the paged-decode kernel:
    one call a layer, no gathered copy of the pages, no pass over the
    pool or a layer of it, fewer temporaries than the gather needed."""
    text = compiled.as_text()
    calls = re.findall(r"^\s*(?:ROOT )?%paged_decode[\w.]* = .*custom-call\(",
                       text, re.M)
    assert len(calls) == n_layer, len(calls)
    layers, pages, page, width = pool.shape
    gathered = re.findall(
        rf"= \w+\[16,\d+,{page},{width}\]\S* (?:gather|fusion)\(", text)
    assert not gathered, gathered[:2]
    shapes = (",".join(map(str, pool.shape)),
              ",".join(map(str, pool.shape[1:])))
    passes = [line.strip()[:120] for line in text.splitlines()
              if (m := _POOL_PASS.match(line)) and m.group(1) in shapes]
    assert not passes, (len(passes), passes[:4])
    assert compiled.memory_analysis().temp_size_in_bytes < temp_bytes


def test_large_decode_cell_attends_through_the_paged_kernel(
        engine_program):
    """The decode program of serve-gpt2-large-sat ([16, 1] over 1024
    pages) as the backend ``tpu`` builds it: 36 ``paged_decode`` calls on
    the pool where it lies.  The gather's program held 0.361 GB of
    temporaries (PERF.md, PR 28)."""
    compiled, pool = engine_program("large", (16, 1), 1024, kernel=True)
    _assert_attends_in_place(compiled, pool, 36, 0.3e9)


@pytest.mark.parametrize("kernel", [True, False],
                         ids=["flash", "dense"])
def test_prefill_program_attends_among_its_own_rows(engine_program, kernel):
    """A prefill (T > 1) stores its K/V and attends among its OWN rows
    (models/attention.py since PR 48): as the backend ``tpu`` dispatches,
    through the flash kernel once a layer; either way the program reads
    nothing back from the pool (no gather of a sequence's pages: its
    32,768 positions of 12 heads would be [1, 32768, 768] a layer), calls
    no ``paged_decode`` and holds no score array over ``max_context``."""
    compiled, pool = engine_program("124m", (1, 512), 2048, kernel=kernel)
    text = compiled.as_text()
    assert "paged_decode" not in text
    assert _kernel_calls(text)["flash_fwd"] == (12 if kernel else 0)
    positions = 2048 * 16
    assert not re.search(rf"\[[\d,]*\b{positions}\b[\d,]*\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1e9


@pytest.mark.parametrize("h,h_kv,d,dtype", [
    (32, 8, 128, jnp.bfloat16), (12, 12, 64, jnp.bfloat16),
    (12, 12, 64, jnp.float32)],
    ids=["gqa_32_over_8_of_128", "mha_12_of_64", "mha_12_of_64_float32"])
def test_paged_decode_kernel_compiles(one_chip, h, h_kv, d, dtype):
    """The kernel alone at widths no cell has: grouped-query heads (four
    query heads a K/V head, Llama-3-8B's), GPT-2 124M's (chip_smoke.py's
    replica), and a float32 pool."""
    from ray_tpu.ops.paged_attention import paged_decode, supported

    on = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    pool = on((4, 512, 16, h_kv * d), dtype)
    assert supported(on((16, 1, h, d), dtype), pool)
    compiled = jax.jit(functools.partial(
        paged_decode, layer=2, interpret=False)).lower(
        on((16, 1, h, d), dtype), pool, pool,
        page_table=on((16, 64), jnp.int32),
        lengths=on((16,), jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


@pytest.mark.parametrize("h,d_k,d_v,block", [
    (32, 128, 128, None), (12, 128, 128, 4), (8, 256, 128, None)],
    ids=["cell_32_heads_of_128", "three_blocks_of_4_heads", "d_k_256"])
def test_kda_step_kernel_compiles(one_chip, h, d_k, d_v, block):
    """The delta rule's decode step alone (ops/delta_rule.py): at the
    Kimi-Linear cell's sizes (16 rows on a pool [20, 16, 32, 128, 128],
    blocks of 16 heads), with an odd number of blocks a row (the buffers
    then alternate across the rows), and with states of 256 x 128.  One
    kernel, the donated pool aliased through it and never copied."""
    from ray_tpu.ops import delta_rule

    on = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    pool = on((20, 16, h, d_k, d_v), jnp.float32)
    row = on((16, h, d_k), jnp.float32)
    assert delta_rule.supported(pool, row)
    blocks = {} if block is None else {"block_heads": block}
    compiled = jax.jit(functools.partial(
        delta_rule.kda_step, layer=3, interpret=False, **blocks),
        donate_argnums=(0,)).lower(
        pool, slots=on((16,), jnp.int32), fresh=on((16,), jnp.bool_),
        q=row, k=row, v=on((16, h, d_v), jnp.float32), a=row,
        beta=on((16, h), jnp.float32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert re.search(r"^\s*(?:ROOT )?%kda_step[\w.]* = .*custom-call\(", text,
                     re.M)
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes == 20 * 16 * h * d_k * d_v * 4
    assert m.temp_size_in_bytes < 4e6


@pytest.mark.parametrize("program", ["sample", "last"])
def test_engine_sampler_compiles_at_the_cells_sizes(one_chip, program):
    """The engine's sampler (llm/sampling.py) at max_batch 16 and GPT-2's
    vocabulary: a search, no sort (the TPU compiler takes 20-30 s to
    compile a sort of 50,257 values: that would be every replica's
    set-up), int32 ids out, a few MB of temporaries; and the placement
    of a prefill's one row into the sampler's shape, ONE program whatever
    the bucket."""
    import time

    from ray_tpu.llm.sampling import jit_sampler

    sampler, last_rows = jit_sampler(16)
    on = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    vocab = GPT2_124M["vocab_size"]
    t0 = time.perf_counter()
    if program == "sample":
        compiled = sampler.lower(
            on((16, 1, vocab), jnp.float32), on((16, 2), jnp.float32),
            on((16, 4), jnp.uint32)).compile()
        out, = jax.tree_util.tree_leaves(compiled.out_info)
        assert (out.shape, out.dtype) == ((16,), jnp.int32)
    else:
        compiled = last_rows.lower(on((1, 1, vocab), jnp.float32)).compile()
        assert _logits_shape(compiled) == (16, 1, vocab)
    assert time.perf_counter() - t0 < 15
    assert not re.search(r" sort\(", compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes < 32e6


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["gather", "paged_kernel"])
def test_olmoe_decode_cell_compiles_with_grouped_matmuls_in_place(
        one_chip, kernel):
    """The decode program of serve-olmoe-1b-7b-sat (OLMoE-1B-7B's widths,
    8 layers, bf16 weights, [16, 1] over 1024 pages, max_context 1024):
    it fits the chip; every expert matmul is the compiler's own grouped
    kernel (per layer one metadata call and three matmuls: a dense
    fallback over all 64 experts would show none); and the pool is
    updated where it lies, as for the dense models."""
    from ray_tpu.llm.engine import jit_forward
    from ray_tpu.llm.kv_cache import init_cache, pages_for
    from ray_tpu.models.llama import Llama, LlamaConfig, llama_init

    cfg = LlamaConfig.olmoe_1b_7b(n_layer=8, attn_impl="dense",
                                  remat=False)
    params = jax.eval_shape(lambda: llama_init(cfg, jax.random.PRNGKey(0)))
    assert {x.dtype for x in jax.tree_util.tree_leaves(params)} == {
        jnp.dtype(jnp.bfloat16)}
    kv = jax.eval_shape(lambda: init_cache(
        cfg.n_layer, 1024, 16, cfg.n_kv_head, cfg.d_model // cfg.n_head,
        cfg.dtype))
    ints = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                             sharding=one_chip)
    with _as_on_tpu(kernel):
        compiled = jit_forward(Llama(cfg)).lower(
            _on(params, one_chip), ints((16, 1)),
            _on(kv["k_pages"], one_chip), _on(kv["v_pages"], one_chip),
            ints((16, pages_for(1024, 16))), ints((16, 1))).compile()
    assert _device_bytes(compiled) < HBM_BYTES
    text = compiled.as_text()
    assert text.count('op_name="ragged-dot-metadata"') == cfg.n_layer
    assert text.count("ragged_dot_tiling") == 3 * cfg.n_layer
    # By shape, not by size: the rows gathered for attention (16 x 1024
    # positions) have as many elements as one layer of this pool.
    pool = kv["k_pages"]
    shapes = (",".join(map(str, pool.shape)),
              ",".join(map(str, pool.shape[1:])))
    passes = [line.strip()[:120] for line in text.splitlines()
              if (m := _POOL_PASS.match(line)) and m.group(1) in shapes]
    assert not passes, (len(passes), passes[:4])
    # 0.28 GB: one layer's gathered K and V in float32, a quarter of an
    # 8-layer pool array each; a second pool would be 1.07 GB.
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 0.6 * pool.size * pool.dtype.itemsize
    if kernel:      # as the backend ``tpu`` builds it: nothing gathered
        _assert_attends_in_place(compiled, pool, cfg.n_layer, 0.2e9)


@pytest.mark.parametrize("cell", ["serve-gpt2-large-sat",
                                  "serve-olmoe-1b-7b-sat"])
def test_a_cells_prefill_returns_the_one_position_it_serves(one_chip, cell):
    """The two serving cells whose prefill no other test here compiles,
    LOWERED at the cell's sizes as the engine calls it (the other five
    cells' tests hold their compiled prefill to the same): the first
    output is one position's logits and no array of ``bucket x V``
    elements is in the program."""
    from ray_tpu.llm.engine import jit_forward
    from ray_tpu.llm.kv_cache import init_cache, pages_for

    shape = (1, 256)
    ints = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                             sharding=one_chip)
    if cell == "serve-gpt2-large-sat":
        from ray_tpu.models.gpt2 import GPT2, GPT2Config

        cfg = GPT2Config(**ENGINE_WIDTHS["large"], attn_impl="dense",
                         remat=False)
        model, args = GPT2(cfg), _engine_args(cfg, one_chip, shape,
                                              num_pages=1024)
    else:
        from ray_tpu.models.llama import Llama, LlamaConfig, llama_init

        cfg = LlamaConfig.olmoe_1b_7b(n_layer=8, attn_impl="dense",
                                      remat=False)
        kv = jax.eval_shape(lambda: init_cache(
            cfg.n_layer, 1024, 16, cfg.n_kv_head,
            cfg.d_model // cfg.n_head, cfg.dtype))
        model, args = Llama(cfg), (
            _on(jax.eval_shape(lambda: llama_init(
                cfg, jax.random.PRNGKey(0))), one_chip), ints(shape),
            _on(kv["k_pages"], one_chip), _on(kv["v_pages"], one_chip),
            ints((1, pages_for(1024, 16))), ints(shape))
    lowered = jit_forward(model).lower(*args, **_served(shape, one_chip))
    assert _logits_shape(lowered) == (1, 1, cfg.vocab_size)
    assert f"x{shape[1]}x{cfg.vocab_size}x" not in lowered.as_text()
    # ... which the all-rows form, the positional call, still returns
    assert _logits_shape(jit_forward(model).lower(*args)) == shape + (
        cfg.vocab_size,)


@pytest.mark.parametrize("tokens_shape", [(16, 1), (1, 256), (1, 512)],
                         ids=["decode", "prefill_1chunk", "prefill_2chunks"])
def test_granite_cell_updates_both_caches_in_place(one_chip, tokens_shape):
    """The programs of serve-granite-4.0-h-small-sat at the published
    widths (one period of the layer pattern: 9 state-space layers and 1
    with attention; 18 of 72 experts held; bf16; max_batch 16 slots, 1024
    pages, max_context 1024), as the backend ``tpu`` builds them: they
    fit the chip; the K/V pool (of the ONE attention layer) and the state
    pool (``conv``, ``ssm`` float32 [9, 16, 128, 64, 128]) are aliased to
    the outputs and updated where they lie: no copy of a pool or of a
    layer of it, and temporaries of less than a twentieth of the state
    pool for the decode step (a gathered batch of states is a ninth) and
    a third for a prefill (activations, which lay in part inside the
    buffer of every row's logits while a prefill returned those; the pool re-laid for its
    update, as a one-chunk prefill first had it, is all of it); the
    attention layer's decode step goes through the paged kernel with
    Granite's scale; the experts run as the compiler's grouped kernels
    over the 18 held."""
    from ray_tpu.llm.engine import jit_forward
    from ray_tpu.llm.kv_cache import init_cache, init_state, pages_for
    from ray_tpu.models import MODEL_FAMILIES
    from ray_tpu.models.granite import GraniteConfig

    row = MODEL_FAMILIES["granitemoehybrid"]
    cfg = GraniteConfig(layer_types=GraniteConfig().layer_types[:10],
                        held_experts=18, max_seq=1024, attn_impl="dense",
                        remat=False)
    spec = row.cache(cfg)
    assert (spec.kv_layers, spec.state_layers) == (1, 9)
    params = jax.eval_shape(lambda: row.init(cfg, jax.random.PRNGKey(0)))
    kv = jax.eval_shape(lambda: init_cache(
        spec.kv_layers, 1024, 16, spec.kv_heads, spec.head_dim, cfg.dtype))
    state = jax.eval_shape(lambda: init_state(spec, 16, cfg.dtype))
    b = tokens_shape[0]
    ints = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                             sharding=one_chip)
    with _as_on_tpu():
        compiled = jit_forward(row.module(cfg)).lower(
            _on(params, one_chip), ints(tokens_shape),
            _on(kv["k_pages"], one_chip), _on(kv["v_pages"], one_chip),
            ints((b, pages_for(1024, 16))), ints(tokens_shape),
            _on(state["conv"], one_chip), _on(state["ssm"], one_chip),
            ints((b,)), **_served(tokens_shape, one_chip)).compile()
    assert _device_bytes(compiled) < HBM_BYTES
    assert _logits_shape(compiled) == (b, 1, cfg.vocab_size)
    m = compiled.memory_analysis()
    pools = [kv["k_pages"], kv["v_pages"], state["conv"], state["ssm"]]
    assert m.alias_size_in_bytes == sum(
        a.size * a.dtype.itemsize for a in pools)
    ssm = state["ssm"]
    share = 0.05 if tokens_shape[1] == 1 else 0.33
    assert m.temp_size_in_bytes < share * ssm.size * ssm.dtype.itemsize, \
        m.temp_size_in_bytes / (ssm.size * ssm.dtype.itemsize)
    text = compiled.as_text()
    # (a layer of ``conv`` has the shape of a decode batch's windows)
    shapes = {",".join(map(str, shape)) for a in pools
              for shape in (a.shape, a.shape[1:])} - {
        ",".join(map(str, state["conv"].shape[1:]))}
    copies = [line.strip()[:120] for line in text.splitlines()
              if (hit := re.match(r"^\s*(?:ROOT )?%?[\w.-]+ = \w+\[([\d,]+)\]"
                                  r"\S* (copy|copy-done|transpose)\(", line))
              and hit.group(1) in shapes]
    assert not copies, (len(copies), copies[:4])
    # (a prefill holds a share's two branches a layer, ops/moe.py)
    assert text.count('op_name="ragged-dot-metadata"') == cfg.n_layer * (
        1 if tokens_shape[1] == 1 else 2)
    kernels = re.findall(
        r"^\s*(?:ROOT )?%paged_decode[\w.]* = .*custom-call\(", text, re.M)
    assert len(kernels) == (1 if tokens_shape[1] == 1 else 0)


@pytest.mark.parametrize("tokens_shape", [(16, 1), (1, 1024)],
                         ids=["decode", "prefill_1024"])
def test_lfm2_cell_compiles_at_the_benchmarks_sizes(one_chip, tokens_shape):
    """The decode and ``prefill[1024]`` programs of serve-lfm2-24b-a2b-sat
    at the benchmark's sizes (layers 0-9 as published: 8 short-conv and 2
    attention layers, 2 dense FFNs and 8 x 64 experts, the whole
    vocabulary; bf16; max_batch 16 slots, 1024 pages, max_context 1024),
    as the backend ``tpu`` builds them: 10.53 GB of weights, under the
    chip's 16 GB (a 1024-position prefill returns the one position it
    serves: 10.76 GB, where every row's float32 logits made 10.92); the
    K/V pool (of the 2 attention layers) and the state pool, which is the
    ``conv`` array and nothing else ([8, 16, 2, 2048] bf16: 8 KB a
    sequence a layer), aliased to the outputs; the attention layers'
    decode step through the paged kernel at 8 K/V heads of 64; the
    experts as the compiler's grouped kernels in the 8 layers that have
    them."""
    from ray_tpu.llm.engine import jit_forward
    from ray_tpu.llm.kv_cache import init_cache, init_state, pages_for
    from ray_tpu.models import MODEL_FAMILIES
    from ray_tpu.models.lfm2 import Lfm2Config

    row = MODEL_FAMILIES["lfm2moe"]
    cfg = Lfm2Config(layer_types=Lfm2Config().layer_types[:10],
                     attn_impl="dense", remat=False)
    spec = row.cache(cfg)
    assert (spec.kv_layers, spec.state_layers, spec.ssm_shape) == (2, 8, ())
    params = jax.eval_shape(lambda: row.init(cfg, jax.random.PRNGKey(0)))
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    assert abs(weights - 10.53e9) < 0.01 * 10.53e9
    kv = jax.eval_shape(lambda: init_cache(
        spec.kv_layers, 1024, 16, spec.kv_heads, spec.head_dim, cfg.dtype))
    state = jax.eval_shape(lambda: init_state(spec, 16, cfg.dtype))
    assert list(state) == ["conv"]
    assert state["conv"].shape == (8, 16, 2, 2048)
    b = tokens_shape[0]
    ints = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                             sharding=one_chip)
    with _as_on_tpu():
        compiled = jit_forward(row.module(cfg)).lower(
            _on(params, one_chip), ints(tokens_shape),
            _on(kv["k_pages"], one_chip), _on(kv["v_pages"], one_chip),
            ints((b, pages_for(1024, 16))), ints(tokens_shape),
            _on(state["conv"], one_chip), ints((b,)),
            **_served(tokens_shape, one_chip)).compile()
    assert _device_bytes(compiled) < (10.7e9 if b > 1 else 10.8e9)
    assert _logits_shape(compiled) == (b, 1, cfg.vocab_size)
    m = compiled.memory_analysis()
    pools = [kv["k_pages"], kv["v_pages"], state["conv"]]
    assert m.alias_size_in_bytes == sum(
        a.size * a.dtype.itemsize for a in pools)
    assert m.temp_size_in_bytes < (0.1e9 if b > 1 else 0.2e9)
    text = compiled.as_text()
    assert text.count('op_name="ragged-dot-metadata"') == cfg.n_moe_layers
    kernels = re.findall(
        r"^\s*(?:ROOT )?%paged_decode[\w.]* = .*custom-call\(", text, re.M)
    assert len(kernels) == (2 if tokens_shape[1] == 1 else 0)


@pytest.mark.parametrize("tokens_shape", [(16, 1), (1, 4096)],
                         ids=["decode", "prefill4096"])
def test_kimi_cell_compiles_at_the_benchmarks_sizes(one_chip, tokens_shape):
    """The decode and ``prefill[4096]`` programs of serve-kimi-k2.5-4k at
    the benchmark's sizes (layer 0, dense, and six sparse layers at the
    published widths; 12 of 384 experts held, a router of 384; an eighth
    of the vocabulary; bf16; max_batch 16, 4096 pages of 16, max_context
    4096), as the backend ``tpu`` builds them: 9.70 GB of weights and ONE
    latent pool [7, 4096, 16, 640] (0.59 GB: a position is 1,280 bytes a
    layer), aliased to the output, no V pool beside it.  The decode step
    attends through the latent paged kernel, once a layer, and makes no
    key or value of any head; the prefill attends through the flash
    kernel at heads of 192 (v padded), once a layer, reads nothing from
    the pool, and holds under 1.5 GB of temporaries: no ``[T, T]`` score
    array (64 x 4096 x 4096 float32 would be 4.3 GB a layer)."""
    import ray_tpu.models.attention as attention
    import ray_tpu.ops
    from ray_tpu.llm.engine import jit_forward
    from ray_tpu.llm.kv_cache import init_pool, pages_for
    from ray_tpu.models import MODEL_FAMILIES
    from ray_tpu.models.kimi import KimiK2Config
    from ray_tpu.ops import paged_attention

    row = MODEL_FAMILIES["kimik2"]
    cfg = KimiK2Config(vocab_size=20480, n_layer=7, held_experts=12,
                       attn_impl="dense", remat=False)
    spec = row.cache(cfg)
    params = jax.eval_shape(lambda: row.init(cfg, jax.random.PRNGKey(0)))
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    assert abs(weights - 9.70e9) < 0.01 * 9.70e9
    kv = jax.eval_shape(lambda: init_pool(spec, 4096, 16, cfg.dtype))
    assert list(kv) == ["latent_pages"]
    assert kv["latent_pages"].shape == (7, 4096, 16, 640)
    b = tokens_shape[0]
    ints = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                             sharding=one_chip)
    with pytest.MonkeyPatch.context() as patch:    # as on the tpu backend
        patch.setattr(attention, "_latent_kernel",
                      paged_attention.latent_supported)
        patch.setattr(paged_attention, "paged_decode_latent",
                      functools.partial(paged_attention.paged_decode_latent,
                                        interpret=False))
        patch.setattr(attention, "_prefill_impl", lambda t: "flash")
        patch.setattr(ray_tpu.ops, "flash_attention", functools.partial(
            ray_tpu.ops.flash_attention, interpret=False))
        lowered = jit_forward(row.module(cfg)).lower(
            _on(params, one_chip), ints(tokens_shape),
            _on(kv["latent_pages"], one_chip),
            ints((b, pages_for(4096, 16))), ints(tokens_shape),
            **_served(tokens_shape, one_chip))
        compiled = lowered.compile()
    decode = tokens_shape[1] == 1
    assert _device_bytes(compiled) < (10.6e9 if decode else 11.6e9)
    assert _logits_shape(compiled) == (b, 1, cfg.vocab_size)
    m = compiled.memory_analysis()
    pool = kv["latent_pages"]
    assert m.alias_size_in_bytes == pool.size * pool.dtype.itemsize
    assert m.temp_size_in_bytes < (0.1e9 if decode else 1.5e9)
    text = compiled.as_text()
    # The experts' branch (ops/moe.py): none in the decode step (128
    # pairs); in the prefill one a sparse layer, whose compact branch
    # runs over 2,176 rows and holds no array of all 32,768 pairs' rows.
    branches = re.findall(
        r'"stablehlo\.case"\(.*?\) \(\{\n(.*?)\n\s*\}, \{\n(.*?)\n\s*\}\) :',
        lowered.as_text(), re.S)
    assert len(branches) == (0 if decode else cfg.n_moe_layers)
    for plain, compact in branches:
        assert "tensor<32768x7168xbf16>" in plain
        assert "tensor<2176x7168xbf16>" in compact
        assert not re.search(r"tensor<32768x\d+x", compact)
    assert text.count('op_name="ragged-dot-metadata"') == \
        cfg.n_moe_layers * (1 if decode else 2)

    def calls(kernel):
        return len(re.findall(
            rf"^\s*(?:ROOT )?%{kernel}[\w.]* = .*custom-call\(", text,
            re.M))

    assert calls("paged_decode_latent") == (7 if decode else 0)
    assert calls("flash_fwd") == (0 if decode else 7)


@pytest.mark.parametrize("tokens_shape", [(16, 1), (1, 4096)],
                         ids=["decode", "prefill4096"])
def test_xing_cell_compiles_at_the_benchmarks_sizes(one_chip, tokens_shape):
    """The decode and ``prefill[4096]`` programs of
    serve-xing4.0-29b-a4b-4k at the benchmark's sizes (layers 0-1, dense,
    and five sparse layers at the published widths carried as FOUR residual
    streams; ALL 64 experts held; the WHOLE vocabulary; bf16; max_batch 16,
    4096 pages of 16, max_context 4096), as the backend ``tpu`` builds
    them: 9.85 GB of weights and Kimi-K2's ONE latent pool [7, 4096, 16,
    640] (0.59 GB), aliased to the output: the streams live inside a step,
    nothing of them is cached.  Every layer holds every expert, so no
    program has the experts' compact branch (one grouped matmul's metadata
    a sparse layer); the decode step attends through the latent paged
    kernel and the prefill through the flash kernel, once a layer; the
    prefill holds no ``[T, T]`` score array (32 x 4096 x 4096 float32 would
    be 2.1 GB a layer) and returns the logits of the ONE position it
    serves: 10.998 GB, 65% of the chip's 16.9 GB, where every row's
    float32 logits (2.147 GB) made it 13.033 GB (the accepted
    benchmark/cells/serve-xing4.0-29b-a4b-4k.json ``sized`` still says
    so; the fault tools prefill through that form)."""
    import ray_tpu.models.attention as attention
    import ray_tpu.ops
    from ray_tpu.llm.engine import jit_forward
    from ray_tpu.llm.kv_cache import init_pool, pages_for
    from ray_tpu.models import MODEL_FAMILIES
    from ray_tpu.models.xing import XingConfig
    from ray_tpu.ops import paged_attention

    row = MODEL_FAMILIES["xing40"]
    cfg = XingConfig(n_layer=7, attn_impl="dense", remat=False)
    spec = row.cache(cfg)
    params = jax.eval_shape(lambda: row.init(cfg, jax.random.PRNGKey(0)))
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    assert abs(weights - 9.85e9) < 0.01 * 9.85e9
    kv = jax.eval_shape(lambda: init_pool(spec, 4096, 16, cfg.dtype))
    assert list(kv) == ["latent_pages"]
    assert kv["latent_pages"].shape == (7, 4096, 16, 640)
    b = tokens_shape[0]
    ints = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                             sharding=one_chip)
    with pytest.MonkeyPatch.context() as patch:    # as on the tpu backend
        patch.setattr(attention, "_latent_kernel",
                      paged_attention.latent_supported)
        patch.setattr(paged_attention, "paged_decode_latent",
                      functools.partial(paged_attention.paged_decode_latent,
                                        interpret=False))
        patch.setattr(attention, "_prefill_impl", lambda t: "flash")
        patch.setattr(ray_tpu.ops, "flash_attention", functools.partial(
            ray_tpu.ops.flash_attention, interpret=False))
        lowered = jit_forward(row.module(cfg)).lower(
            _on(params, one_chip), ints(tokens_shape),
            _on(kv["latent_pages"], one_chip),
            ints((b, pages_for(4096, 16))), ints(tokens_shape),
            **_served(tokens_shape, one_chip))
        compiled = lowered.compile()
    decode = tokens_shape[1] == 1
    total = _device_bytes(compiled)
    m = compiled.memory_analysis()
    pool = kv["latent_pages"]
    assert m.alias_size_in_bytes == pool.size * pool.dtype.itemsize
    if decode:      # the parent's program, to the byte
        assert total == 10_475_834_368 and m.temp_size_in_bytes < 0.2e9
    else:       # 13,033,469,952 with every row's logits (2.147 GB of it)
        assert total == 10_998_351_360, total
        assert m.temp_size_in_bytes < 0.6e9     # no [T, T] array
    text = compiled.as_text()
    assert "stablehlo.case" not in lowered.as_text()
    assert text.count('op_name="ragged-dot-metadata"') == cfg.n_moe_layers
    # the outputs: logits, the pool, the routing counters, the maps' errors
    assert [tuple(o.shape) for o in jax.tree_util.tree_leaves(
        compiled.out_info)] == [
        (b, 1, 131072), pool.shape, (cfg.n_moe_layers, 4), (2,)]

    def calls(kernel):
        return len(re.findall(
            rf"^\s*(?:ROOT )?%{kernel}[\w.]* = .*custom-call\(", text,
            re.M))

    assert calls("paged_decode_latent") == (7 if decode else 0)
    assert calls("flash_fwd") == (0 if decode else 7)


@pytest.mark.parametrize("tokens_shape", [(16, 1), (1, 2048)],
                         ids=["decode", "prefill2048"])
def test_kimi_linear_cell_compiles_at_the_benchmarks_sizes(one_chip,
                                                           tokens_shape):
    """The decode and ``prefill[2048]`` programs of
    serve-kimi-linear-48b-a3b-longout at the benchmark's sizes (all 27
    layers at the published widths: 20 KDA mixers of 32 heads of 128, 7
    latent layers; 16 of 256 experts held, a router of 256; the WHOLE
    vocabulary; bf16; max_batch 16, 4096 pages of 16, max_context 4096),
    as the backend ``tpu`` builds them: 9.91 GB of weights, ONE latent
    pool [7, 4096, 16, 640] and the state pool's ``conv`` [20, 16, 3,
    12288] and float32 ``ssm`` [20, 16, 32, 128, 128], all three aliased
    to the outputs.  The decode step attends through the latent paged
    kernel once a latent layer and runs the recurrence through the
    ``kda_step`` kernel once a KDA layer, the state pool handed from call
    to call as the ONE buffer it came in (each call aliases it in and out
    and copies the running rows' states itself): no copy of the pool, no
    slice or in-place write of a slab, no loop over the rows; the prefill
    runs the chunked scan (batched matmuls and one short loop a KDA layer)
    and the flash kernel, and holds under 1 GB of temporaries; the logits
    are the one served position's (every row's were 1.34 GB in float32)."""
    import ray_tpu.models.attention as attention
    import ray_tpu.models.kimi_linear as kimi_linear
    import ray_tpu.ops
    from ray_tpu.llm.engine import jit_forward
    from ray_tpu.llm.kv_cache import init_pool, init_state, pages_for
    from ray_tpu.models import MODEL_FAMILIES
    from ray_tpu.models.kimi_linear import KimiLinearConfig
    from ray_tpu.ops import delta_rule, paged_attention

    row = MODEL_FAMILIES["kimilinear"]
    cfg = KimiLinearConfig(held_experts=16, attn_impl="dense", remat=False)
    spec = row.cache(cfg)
    params = jax.eval_shape(lambda: row.init(cfg, jax.random.PRNGKey(0)))
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    assert abs(weights - 9.915e9) < 0.001 * 9.915e9
    kv = jax.eval_shape(lambda: init_pool(spec, 4096, 16, cfg.dtype))
    state = jax.eval_shape(lambda: init_state(spec, 16, cfg.dtype))
    assert kv["latent_pages"].shape == (7, 4096, 16, 640)
    assert state["conv"].shape == (20, 16, 3, 12288)
    assert state["ssm"].shape == (20, 16, 32, 128, 128)
    b = tokens_shape[0]
    ints = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                             sharding=one_chip)
    with pytest.MonkeyPatch.context() as patch:    # as on the tpu backend
        patch.setattr(attention, "_latent_kernel",
                      paged_attention.latent_supported)
        patch.setattr(paged_attention, "paged_decode_latent",
                      functools.partial(paged_attention.paged_decode_latent,
                                        interpret=False))
        patch.setattr(attention, "_prefill_impl", lambda t: "flash")
        patch.setattr(ray_tpu.ops, "flash_attention", functools.partial(
            ray_tpu.ops.flash_attention, interpret=False))
        patch.setattr(kimi_linear, "_step_kernel", delta_rule.supported)
        patch.setattr(delta_rule, "kda_step", functools.partial(
            delta_rule.kda_step, interpret=False))
        compiled = jit_forward(row.module(cfg)).lower(
            _on(params, one_chip), ints(tokens_shape),
            _on(kv["latent_pages"], one_chip),
            ints((b, pages_for(4096, 16))), ints(tokens_shape),
            _on(state["conv"], one_chip), _on(state["ssm"], one_chip),
            ints((b,)), **_served(tokens_shape, one_chip)).compile()
    decode = tokens_shape[1] == 1
    assert _device_bytes(compiled) < (11.5e9 if decode else 12.0e9)
    assert _logits_shape(compiled) == (b, 1, cfg.vocab_size)
    m = compiled.memory_analysis()
    pools = [kv["latent_pages"], state["conv"], state["ssm"]]
    assert m.alias_size_in_bytes == sum(a.size * a.dtype.itemsize
                                        for a in pools)
    assert m.temp_size_in_bytes < (0.2e9 if decode else 1.0e9)
    text = compiled.as_text()
    assert text.count('op_name="ragged-dot-metadata"') == \
        cfg.n_moe_layers * (1 if decode else 2)
    # no pass over the state pool: a prefill writes one row in place (a
    # dynamic-update-slice a KDA layer); a decode step makes the pool
    # nowhere but as the 20 kernels' aliased result, and a layer's slab
    # nowhere at all
    shape = ",".join(map(str, state["ssm"].shape))
    slab = ",".join(map(str, state["ssm"].shape[1:]))
    passes = [line.strip()[:120] for line in text.splitlines()
              if (hit := _POOL_PASS.match(line))
              and hit.group(1) in (shape, slab)
              and (decode or hit.group(2) != "dynamic-update-slice")]
    assert not passes, (len(passes), passes[:4])

    def calls(kernel):
        return len(re.findall(
            rf"^\s*(?:ROOT )?%{kernel}[\w.]* = .*custom-call\(", text,
            re.M))

    assert calls("paged_decode_latent") == (7 if decode else 0)
    assert calls("flash_fwd") == (0 if decode else 7)
    assert calls("kda_step") == (20 if decode else 0)
    _assert_scan_is_matmuls(text, "kda.scan", 0 if decode else 20)
    # what MAKES an array of the pool's shape (not a parameter, an element
    # of a tuple or the tuple returned): instruction and opcode
    made = [(hit.group(1), hit.group(2)) for line in text.splitlines()
            if (hit := re.match(
                rf"^\s*(?:ROOT )?%([\w.-]+) = \(?[^=]*?f32\[{shape}\][^=]*?"
                r"[})] ([\w-]+)\(", line))
            and hit.group(2) not in ("parameter", "get-tuple-element",
                                     "tuple")]
    if decode:      # the gathers and scatters were loops over the rows
        assert not re.search(r"^\s*%?[\w.]+ = .* while\(", text, re.M)
        assert len(made) == 20 and all(
            name.startswith("kda_step") and code == "custom-call"
            for name, code in made), made
        assert f"f32[{slab}]" not in text
    else:
        assert "kda.scan" in text and "kda.step" not in text
        assert len(made) >= 20


def test_kda_step_kernel_compiles_at_30_heads_of_96_by_192(one_chip):
    """The delta rule's decode step at Olmo-Hybrid's sizes: 30 heads of 96 x
    192 with ONE decay a head, the pool holding two heads side by side
    ([12, 16, 15, 96, 384]: whole tiles, ops/delta_rule.py state_shape),
    blocks of 5 pairs (three a row: the buffers alternate across the rows).
    One kernel, the donated pool aliased through it and never copied."""
    from ray_tpu.ops import delta_rule

    on = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    shape = delta_rule.state_shape(30, 96, 192)
    assert shape == (15, 96, 384)
    pool = on((12, 16) + shape, jnp.float32)
    row = on((16, 30, 96), jnp.float32)
    assert delta_rule.supported(pool, row)
    compiled = jax.jit(functools.partial(
        delta_rule.kda_step, layer=3, interpret=False),
        donate_argnums=(0,)).lower(
        pool, slots=on((16,), jnp.int32), fresh=on((16,), jnp.bool_),
        q=row, k=row, v=on((16, 30, 192), jnp.float32),
        a=on((16, 30, 1), jnp.float32),
        beta=on((16, 30), jnp.float32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert re.search(r"^\s*(?:ROOT )?%kda_step[\w.]* = .*custom-call\(", text,
                     re.M)
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes == 12 * 16 * 30 * 96 * 192 * 4
    assert m.temp_size_in_bytes < 4e6
    assert _logits_shape(compiled) == (16, 30, 192)


@pytest.mark.parametrize("tokens_shape", [(16, 1), (1, 4096)],
                         ids=["decode", "prefill4096"])
def test_olmo_hybrid_cell_compiles_at_the_benchmarks_sizes(one_chip,
                                                           tokens_shape):
    """The decode and ``prefill[4096]`` programs of serve-olmo-hybrid-7b-4k
    at the benchmark's sizes (16 layers at the published widths: 12 Gated
    DeltaNet mixers of 30 heads of 96 x 192, 4 full-attention layers of 30
    heads of 128; the WHOLE vocabulary; bf16; max_batch 16, 4096 pages of
    16, max_context 4096), as the backend ``tpu`` builds them: 8.20 GB of
    weights, K and V pools [4, 4096, 16, 3840] and the state pool's
    ``conv`` [12, 16, 3, 11520] and float32 ``ssm`` [12, 16, 15, 96, 384],
    all four aliased to the outputs; 12.67 GB of arguments, under the
    chip's memory with the prefill's temporaries.  The decode step runs the
    recurrence through the ``kda_step`` kernel once a linear layer (no
    gathered or copied state slab) and attends through the paged kernel
    once an attention layer; the prefill runs the chunked scan (batched
    matmuls and one short loop a linear layer) and the flash kernel among
    its own rows: no ``[.., 4096, 4096]`` float32 array, which the gather
    over ``max_context`` rows made 2 GB a layer of; the logits are the one
    served position's."""
    import ray_tpu.models.kimi_linear as kimi_linear
    from ray_tpu.llm.engine import jit_forward
    from ray_tpu.llm.kv_cache import init_pool, init_state, pages_for
    from ray_tpu.models import MODEL_FAMILIES
    from ray_tpu.models.olmo_hybrid import ATTENTION, GDN, OlmoHybridConfig
    from ray_tpu.ops import delta_rule

    row = MODEL_FAMILIES["olmohybrid"]
    cfg = OlmoHybridConfig(layer_types=(GDN, GDN, GDN, ATTENTION) * 4,
                           attn_impl="dense", remat=False)
    spec = row.cache(cfg)
    params = jax.eval_shape(lambda: row.init(cfg, jax.random.PRNGKey(0)))
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    assert abs(weights - 8.203e9) < 0.001 * 8.203e9
    kv = jax.eval_shape(lambda: init_pool(spec, 4096, 16, cfg.dtype))
    state = jax.eval_shape(lambda: init_state(spec, 16, cfg.dtype))
    assert kv["k_pages"].shape == (4, 4096, 16, 3840)
    assert state["conv"].shape == (12, 16, 3, 11520)
    assert state["ssm"].shape == (12, 16, 15, 96, 384)
    b = tokens_shape[0]
    ints = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                             sharding=one_chip)
    with _as_on_tpu(), pytest.MonkeyPatch.context() as patch:
        patch.setattr(kimi_linear, "_step_kernel", delta_rule.supported)
        patch.setattr(delta_rule, "kda_step", functools.partial(
            delta_rule.kda_step, interpret=False))
        compiled = jit_forward(row.module(cfg)).lower(
            _on(params, one_chip), ints(tokens_shape),
            _on(kv["k_pages"], one_chip), _on(kv["v_pages"], one_chip),
            ints((b, pages_for(4096, 16))), ints(tokens_shape),
            _on(state["conv"], one_chip), _on(state["ssm"], one_chip),
            ints((b,)), **_served(tokens_shape, one_chip)).compile()
    decode = tokens_shape[1] == 1
    assert 0.70 * 16.9e9 < _device_bytes(compiled) < (
        12.8e9 if decode else 13.6e9)
    assert _logits_shape(compiled) == (b, 1, cfg.vocab_size)
    m = compiled.memory_analysis()
    pools = [kv["k_pages"], kv["v_pages"], state["conv"], state["ssm"]]
    assert m.alias_size_in_bytes == sum(a.size * a.dtype.itemsize
                                        for a in pools)
    assert m.temp_size_in_bytes < (0.1e9 if decode else 0.9e9)
    text = compiled.as_text()
    assert not re.search(r"f32\[[\d,]*4096,4096\]", text)
    # no pass over the state pool but a prefill's one row written in place
    shape = ",".join(map(str, state["ssm"].shape))
    slab = ",".join(map(str, state["ssm"].shape[1:]))
    passes = [line.strip()[:120] for line in text.splitlines()
              if (hit := _POOL_PASS.match(line))
              and hit.group(1) in (shape, slab)
              and (decode or hit.group(2) != "dynamic-update-slice")]
    assert not passes, (len(passes), passes[:4])

    def calls(kernel):
        return len(re.findall(
            rf"^\s*(?:ROOT )?%{kernel}[\w.]* = .*custom-call\(", text,
            re.M))

    assert calls("kda_step") == (12 if decode else 0)
    assert calls("paged_decode") == (4 if decode else 0)
    assert calls("flash_fwd") == (0 if decode else 4)
    if decode:
        assert f"f32[{slab}]" not in text
    else:
        assert "gdn.scan" in text and "gdn.step" not in text
    _assert_scan_is_matmuls(text, "gdn.scan", 0 if decode else 12)


def _command_a_cell():
    """serve-command-a-plus-16k's configuration, cache spec and the shapes
    of its weights and both groups of its K/V pool (max_batch 16, 16,384
    pages of 16 in the full group, 16 rings of 256 pages in the window
    group, max_context 16,384)."""
    from ray_tpu.llm.kv_cache import init_pool, ring_pages
    from ray_tpu.models import MODEL_FAMILIES
    from ray_tpu.models.cohere import FULL, SLIDING, Cohere2MoeConfig

    row = MODEL_FAMILIES["cohere2moe"]
    cfg = Cohere2MoeConfig(layer_types=(SLIDING, SLIDING, SLIDING, FULL),
                           held_experts=16, vocab_size=32768,
                           attn_impl="dense", remat=False)
    spec = row.cache(cfg)
    params = jax.eval_shape(lambda: row.init(cfg, jax.random.PRNGKey(0)))
    rings = 16 * ring_pages(spec, 16)
    kv = jax.eval_shape(lambda: init_pool(spec, 16384, 16, cfg.dtype, rings))
    return row, cfg, spec, params, kv


def _command_a_program(one_chip, tokens_shape):
    """One program of the cell as the backend ``tpu`` builds it, compiled
    for the described chip: (compiled, cfg, params, kv)."""
    from ray_tpu.llm.engine import jit_forward
    from ray_tpu.llm.kv_cache import pages_for, pool_arrays

    row, cfg, spec, params, kv = _command_a_cell()
    b = tokens_shape[0]
    ints = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                             sharding=one_chip)
    with _as_on_tpu():
        compiled = jit_forward(row.module(cfg)).lower(
            _on(params, one_chip), ints(tokens_shape),
            *(_on(kv[name], one_chip) for name in pool_arrays(spec)),
            ints((b, pages_for(16384, 16))),
            ints((b, pages_for(spec.window, 16))), ints(tokens_shape),
            **_served(tokens_shape, one_chip)).compile()
    return compiled, cfg, params, kv


@pytest.mark.parametrize("tokens_shape", [(16, 1), (1, 16384)],
                         ids=["decode", "prefill16384"])
def test_command_a_cell_compiles_at_the_benchmarks_sizes(one_chip,
                                                         tokens_shape):
    """The decode and ``prefill[16384]`` programs of
    serve-command-a-plus-16k at the benchmark's sizes (one period of 4
    layers at the published widths: 128 query heads of 128 on 8 K/V heads,
    a window of 4,096, 16 of 128 experts of 4,096 held beside the four
    shared ones, an eighth of the vocabulary; bf16), as the backend ``tpu``
    builds them: 9.47 GB of weights; the K/V pool in TWO groups, the full
    layer's ``k_pages`` / ``v_pages`` [1, 16384, 16, 1024] (16 x 16,384
    positions) and the three window layers' ``window_k_pages`` /
    ``window_v_pages`` [3, 4096, 16, 1024] (16 rings of 4,096 positions:
    what a window layer holds stops at the window), all four aliased to
    the outputs.  The decode step attends through the paged kernel once a
    layer, the rings through the same kernel as the full layer's pages;
    the prefill through the flash kernel once a layer, under the band in
    three of them, K and V read by group where they lie: no ``[.., T, T]``
    score array and no K or V repeated to 128 heads; the logits are the one
    served position's."""
    compiled, cfg, params, kv = _command_a_program(one_chip, tokens_shape)
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    assert abs(weights - 9.47e9) < 0.002 * 9.47e9
    assert kv["k_pages"].shape == (1, 16384, 16, 1024)
    assert kv["window_k_pages"].shape == (3, 4096, 16, 1024)
    b, t = tokens_shape
    decode = t == 1
    assert 0.25 * 16.9e9 < _device_bytes(compiled) < 0.95 * 16.9e9
    assert _logits_shape(compiled) == (b, 1, cfg.vocab_size)
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes == sum(a.size * a.dtype.itemsize
                                        for a in kv.values())
    assert m.temp_size_in_bytes < (0.2e9 if decode else 4.0e9)
    text = compiled.as_text()
    # (q and the output are [1, T, 128 x 128] with T = 16,384 too: a score
    # array has a heads dimension before its two T's)
    assert decode or not re.search(
        rf"(f32|bf16)\[(\d+,)*([2-9]|\d\d+),{t},{t}\]", text)
    # K or V at the query heads' width: a group's head broadcast 16 times,
    # or made [1, T, 128, 128] by anything but q's own projection and RoPE
    repeated = re.findall(rf"bf16\[1,{t},(?:8,16|128),128\]\S* "
                          r"(?:broadcast|concatenate|gather)\(", text)
    assert not repeated, repeated[:3]
    assert len(re.findall(rf"= bf16\[1,{t},128,128\]\S* fusion\(",
                          text)) <= 3         # q after RoPE, three layers

    def calls(kernel):
        return len(re.findall(
            rf"^\s*(?:ROOT )?%{kernel}[\w.]* = .*custom-call\(", text,
            re.M))

    assert calls("paged_decode") == (4 if decode else 0)
    assert calls("flash_fwd") == (0 if decode else 4)
    assert ("attn.window" in text) and ("attn.full" in text)
    if not decode:
        # The compact experts' combine by blocks of 128 tokens
        # (``ops/moe.py combine_blocks``): one loop a layer and no
        # [T, capacity] placement array, fused or not; 15.20 GB as with the
        # one matmul (PR 53: 15.196).
        assert f"[{t},{2 * t + 128}]" not in text
        assert len(re.findall(r" while\(", text)) == 4
        assert _device_bytes(compiled) < 15.21e9


def _train_step_and_shapes(cfg, loss_chunk):
    from ray_tpu.models.gpt2 import gpt2_init, gpt2_loss_fn
    from ray_tpu.train.train_step import TrainState, make_optimizer

    optimizer = make_optimizer(total_steps=100)
    state = jax.eval_shape(lambda: TrainState.create(
        gpt2_init(cfg, jax.random.PRNGKey(0)), optimizer))
    tokens = jax.ShapeDtypeStruct((16, cfg.max_seq + 1), jnp.int32)

    def loss_fn(p, b):
        return gpt2_loss_fn(cfg, p, b, loss_chunk=loss_chunk)

    return loss_fn, optimizer, state, {"tokens": tokens}


def test_gpt2_124m_train_step_compiles_and_fits(one_chip,
                                                compiled_kernel):
    """The whole step chip_smoke.py's train phase runs: batch 16, seq
    1024, bf16, flash kernel, remat, loss_chunk 256, AdamW, donated
    state — under the chip's 16 GB, with the kernel in it."""
    from ray_tpu.models.gpt2 import GPT2Config
    from ray_tpu.train.train_step import make_sharded_train_step

    cfg = GPT2Config(**GPT2_124M, attn_impl="flash", remat=True)
    loss_fn, optimizer, state, batch = _train_step_and_shapes(cfg, 256)
    step = make_sharded_train_step(loss_fn, optimizer, telemetry=False)
    compiled = step.lower(_on(state, one_chip),
                          _on(batch, one_chip)).compile()
    assert _device_bytes(compiled) < HBM_BYTES
    # One kernel of each name a layer (the remat'd block's second
    # forward is merged with the first: ``prevent_cse=False``).
    text = compiled.as_text()
    assert _kernel_calls(text) == dict.fromkeys(KERNEL_NAMES, cfg.n_layer)
    # The kernels read q, k, v where c_attn left them and write where
    # c_proj reads (PR 36): no activation-sized copy or transpose is
    # left under the attention block's scopes (the parent had 14 a
    # layer: the split, the folds and their inverses).
    moved = [line.strip()[:100] for line in text.splitlines()
             if re.match(r"^\s*(?:ROOT )?%?[\w.-]+ = bf16\[[\d,]+\]\S* "
                         r"(copy|transpose)\(", line)
             and re.search(r'op_name="[^"]*/attn\.(qkv|core|out)/', line)
             and np.prod([int(n) for n in re.search(
                 r"bf16\[([\d,]+)\]", line).group(1).split(",")])
             >= 16 * 1024 * 768]
    assert not moved, (len(moved), moved[:4])
    # The chunked loss makes its gradient where it makes its value
    # (PR 42): three vocabulary-sized matmuls under scope loss, in ONE
    # loop; a backward pass that recomputed the logits had four in two.
    under_loss = [line for line in text.splitlines()
                  if re.search(r'op_name="[^"]*[/(]loss[)/]', line)]
    assert sum(bool(re.search(r" (convolution|dot)\(", line))
               for line in under_loss) == 3
    assert sum(" while(" in line for line in under_loss) == 1


def test_gpt2_sharded_step_compiles_for_four_chips(
        mesh_2x2, compiled_kernel):
    """chip_smoke.py --four-chip's step: the GPT-2 state sharded by the
    GPT-2 partition rules over fsdp=2 x tensor=2, every device holding
    its share, collectives on both axes in the compiled program."""
    from ray_tpu.models.gpt2 import GPT2Config
    from ray_tpu.parallel.partition_rules import tree_shardings
    from ray_tpu.train import distributed as dist
    from ray_tpu.train.train_step import make_sharded_train_step
    from ray_tpu.util import xprof

    # The model knows the mesh: the Mosaic kernel is not partitioned
    # automatically and runs per shard inside a shard_map.
    # Full widths, depth cut to 2 layers to keep tier-1 light (the 12-layer
    # program compiled the same way in PR 21's rehearsal, in ~50 s).
    cfg = GPT2Config(**{**GPT2_124M, "n_layer": 2}, attn_impl="flash",
                     remat=True, mesh=mesh_2x2)
    loss_fn, optimizer, state, batch = _train_step_and_shapes(cfg, 256)
    specs = dist.fitted_state_specs(state, mesh_2x2,
                                    dist.rules_for_model("gpt2"))
    # 50257 is odd: the vocab dim stays whole, d_model is still sharded.
    assert specs.params["params"]["wte"] == PartitionSpec(None, "fsdp")
    shardings = tree_shardings(mesh_2x2, specs)
    batch_sharding = NamedSharding(mesh_2x2, PartitionSpec("fsdp"))
    step = make_sharded_train_step(
        loss_fn, optimizer, mesh=mesh_2x2, state_shardings=shardings,
        batch_sharding=batch_sharding, telemetry=False)
    compiled = step.lower(_on(state, shardings),
                          _on(batch, batch_sharding)).compile()
    unsharded = sum(np.prod(s.shape) * s.dtype.itemsize
                    for s in jax.tree_util.tree_leaves(state))
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes < 0.5 * unsharded
    assert _device_bytes(compiled) < HBM_BYTES
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    axes = xprof.summarize_collectives(
        xprof.parse_hlo_collectives(text), dist.mesh_axis_sizes(mesh_2x2))
    for axis in ("fsdp", "tensor"):
        assert sum(a["bytes"] for name, a in axes.items()
                   if axis in name) > 0, axes
    # A block keeps its attention sublayer's summed residual across the
    # remat boundary (PR 50): the FOUR activation-sized sums a layer that
    # tensor parallelism needs, and no sum and no matmul made again; the
    # kernels' second forward is merged with the first, as on one chip.
    from test_parallel import block_sums_and_recomputed

    # (the last block's ``mlp_out`` sum, which feeds the split loss
    # alone, is a reduce-scatter here and counts as the sum it is)
    sums, recomputed = block_sums_and_recomputed(text, cfg.max_seq,
                                                 cfg.d_model)
    assert sums == {f"h_{i}": 4 for i in range(cfg.n_layer)}, sums
    assert not recomputed, recomputed
    assert _kernel_calls(text) == dict.fromkeys(KERNEL_NAMES, cfg.n_layer)
    # The head and its loss on a quarter of the tokens a chip (PR 52): 16
    # rows are 8 an fsdp shard and 4 a chip, so no array holds a batch
    # shard's [8, 1024, V] logits and the largest with the vocabulary is
    # one chunk of a chip's rows; ``d wte`` is summed across the chips
    # once, after the loss's loop.
    _assert_loss_is_split(text, cfg, rows=16, chunk=256)


def _assert_loss_is_split(text, cfg, rows, chunk):
    from test_parallel import summed_across_chips, vocab_arrays

    v, d, t = cfg.vocab_size, cfg.d_model, cfg.max_seq
    arrays = vocab_arrays(text, v)
    assert (rows // 2, t, v) not in arrays
    assert max(int(np.prod(dims)) // v for dims in arrays
               if v * d not in (np.prod(dims), 2 * np.prod(dims))) \
        == rows // 4 * chunk, arrays
    assert summed_across_chips(text, (v, d)) == [False]
    under_loss = [line for line in text.splitlines()
                  if re.search(r'op_name="[^"]*[/(]loss[)/]', line)]
    assert sum(" while(" in line for line in under_loss) == 1


def _cell_step(mesh, n_layer, loss_chunk):
    """``train-gpt2-large-fsdp2x2``'s own step (its ``program_config``,
    its traffic's batch and optimizer, the state placed by the family's
    rules) at ``n_layer`` layers, compiled for the described mesh."""
    import dataclasses

    from benchmark.harness import manifest
    from benchmark.harness.families import family_of
    from ray_tpu.parallel.partition_rules import tree_shardings
    from ray_tpu.train import distributed as dist
    from ray_tpu.train.train_step import (TrainState, make_optimizer,
                                          make_sharded_train_step)

    cell = manifest.load_cell("train-gpt2-large-fsdp2x2")
    fam, tr = family_of(cell.config), cell.traffic
    plain = fam.program_config({**cell.config, "n_layer": n_layer},
                               attn_impl=tr["step"]["attn_impl"],
                               remat=tr["step"]["remat"])
    cfg = dataclasses.replace(plain, mesh=mesh)
    optimizer = make_optimizer(**tr["step"]["optimizer"])
    state = jax.eval_shape(lambda: TrainState.create(
        fam.init(plain, jax.random.PRNGKey(0)), optimizer))
    shardings = tree_shardings(mesh, dist.fitted_state_specs(
        state, mesh, dist.rules_for_model(fam.partition_rules)))
    batch_sharding = NamedSharding(mesh, PartitionSpec("fsdp"))
    step = make_sharded_train_step(
        lambda p, b: fam.loss(cfg, p, b, loss_chunk=loss_chunk), optimizer,
        mesh=mesh, state_shardings=shardings, batch_sharding=batch_sharding,
        telemetry=False)
    batch = {"tokens": jax.ShapeDtypeStruct(
        (tr["global_batch"], tr["seq_len"] + 1), jnp.int32)}
    assert tr["step"]["loss_chunk"] == 256
    return cfg, step.lower(_on(state, shardings),
                           _on(batch, batch_sharding)).compile()


# The cell's 36 layers take ~1.5 min a compile: outside tier-1.  There (my
# compiles, PR 52) 16.009 -> 13.773 GB a chip.
@pytest.mark.parametrize("n_layer,spared", [
    (2, 4.5e9), pytest.param(36, 2.0e9, marks=pytest.mark.slow)])
def test_large_fsdp2x2_cell_keeps_no_whole_logits(mesh_2x2, compiled_kernel,
                                                  n_layer, spared):
    """The four-chip cell's step at its own widths (1,280 wide, 20 heads,
    32 x 1024 tokens, ``loss_chunk`` 256) against the whole-logits program
    of the same tree (``loss_chunk=0``: the parent's path): gigabytes less
    a chip, no ``f32[16,1024,50257]``, the same kernel calls and the same
    four sums a block."""
    cfg, split = _cell_step(mesh_2x2, n_layer, 256)
    _, whole = _cell_step(mesh_2x2, n_layer, 0)
    assert _device_bytes(split) < HBM_BYTES
    assert _device_bytes(whole) - _device_bytes(split) > spared
    text, whole_text = split.as_text(), whole.as_text()
    assert "f32[16,1024,50257]" in whole_text
    assert "f32[16,1024,50257]" not in text
    _assert_loss_is_split(text, cfg, rows=32, chunk=256)
    assert _kernel_calls(text) == _kernel_calls(whole_text) == \
        dict.fromkeys(KERNEL_NAMES, n_layer)
    from test_parallel import block_sums_and_recomputed

    sums, recomputed = block_sums_and_recomputed(text, cfg.max_seq,
                                                 cfg.d_model)
    assert (sums, recomputed) == block_sums_and_recomputed(
        whole_text, cfg.max_seq, cfg.d_model)
    assert sums == {f"h_{i}": 4 for i in range(n_layer)}, sums
