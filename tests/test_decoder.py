"""The seven rows over ``models/decoder.py``: each of the first six keeps
the tree and the cache it had as a model of its own (PR 43, the parent of
the PR that merged them), the seventh (``xing40``, PR 45: the first whose
residual path is not one stream) the tree it came with; all run their
layers through the one loop."""

import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.kv_cache import pool_arrays, state_arrays
from ray_tpu.models import MODEL_FAMILIES, CacheSpec, decoder

# row -> (leaves, sha256[:16] of the tiny tree's "path shape dtype" lines;
# the CacheSpec at the tiny preset; at the published config), all read
# from PR 43's tree: the first from ``init(tiny)``, the other two from its
# ``_attention_only`` / ``_granite_cache`` / ``_lfm2_cache`` /
# ``_kimi_k2_cache`` / ``_kimi_linear_cache``.
PARENT = {
    "llama": ((21, "f4d3b8bd3974719c"),
              (2, 2, 32, 0, (), (), 0, 0),
              (32, 32, 128, 0, (), (), 0, 0)),
    "olmoe": ((27, "28eca959d73e0c8a"),
              (2, 4, 16, 0, (), (), 0, 0),
              (16, 16, 128, 0, (), (), 0, 0)),
    "granitemoehybrid": ((66, "49d6c577b0121f3e"),
                         (1, 2, 16, 3, (3, 96), (4, 16, 16), 0, 0),
                         (4, 8, 128, 36, (3, 8448), (128, 64, 128), 0, 0)),
    "lfm2moe": ((51, "894a6148ae826e5a"),
                (1, 2, 16, 4, (2, 64), (), 0, 0),
                (10, 8, 64, 30, (2, 2048), (), 0, 0)),
    "kimik2": ((49, "f418233d911bc179"),
               (3, 0, 0, 0, (), (), 24, 8),
               (61, 0, 0, 0, (), (), 512, 64)),
    "kimilinear": ((120, "129a7fc464d44d8f"),
                   (2, 0, 0, 4, (3, 192), (4, 16, 16), 24, 8),
                   (7, 0, 0, 20, (3, 12288), (32, 128, 128), 512, 64)),
    # (PR 45's own tree: Kimi-K2's leaves and, a layer, ``attn_hc`` and
    # ``mlp_hc`` of four leaves each)
    "xing40": ((93, "937dcfec63c2317a"),
               (4, 0, 0, 0, (), (), 24, 8),
               (40, 0, 0, 0, (), (), 512, 64)),
    # (PR 48's own tree: the first row whose block norms each sublayer's
    # OUTPUT; K/V for its attention layers beside the delta rule's state,
    # held as pairs of heads at the published widths)
    "olmohybrid": ((121, "79e4d4c6be19723b"),
                   (2, 6, 10, 6, (3, 288), (6, 12, 24), 0, 0),
                   (8, 30, 128, 24, (3, 11520), (15, 96, 384), 0, 0)),
}
ROWS = sorted(PARENT)
# row -> sha256[:16] of the lowered text (``lower().as_text()``, no debug
# info) of the tiny preset's full forward over [2, 16] tokens and of its
# decode step [2, 1] through ``jit_forward`` (16 pages of 4, 2 slots, a
# table of 8 pages), read from PR 47's tree, the parent of the PR that
# taught the block a second norm placement.  (Kimi-Linear's full forward
# holds ``kda_scan``: its text is PR 57's, which wrote the scan as matmuls;
# its decode step, which holds no scan, is still PR 47's.)
PARENT_TEXT = {
    "llama": ("43d357f519b17ee4", "8a441a1186e41b65"),
    "olmoe": ("d6b57ab637dddbdb", "1f5190c8f2beedf4"),
    "granitemoehybrid": ("1861a65bd88f2d2d", "b06880576c4ef440"),
    "lfm2moe": ("3659f4819c814bfa", "f01d30a32d8e8cdb"),
    "kimik2": ("069d35638dcb9998", "3bc9756184d110a6"),
    "kimilinear": ("3544cd92bc534df9", "342c2f867ea51873"),
    "xing40": ("15032390f5e6a9d8", "5af8f70dce770c0f"),
}


def _published(name):
    config = MODEL_FAMILIES[name].config
    if name in ("llama", "olmoe"):
        return {"llama": config.llama2_7b, "olmoe": config.olmoe_1b_7b}[
            name]()
    return config()


@pytest.mark.parametrize("name", ROWS)
def test_the_tiny_tree_is_the_parents(name):
    """Every leaf's path, shape and dtype: what checkpoints, the
    benchmark's references and its loaders walk by name."""
    fam = MODEL_FAMILIES[name]
    shapes = jax.eval_shape(
        lambda: fam.init(fam.tiny(), jax.random.PRNGKey(0)))
    lines = [
        "/".join(str(getattr(p, "key", p)) for p in path)
        + f" {tuple(leaf.shape)} {leaf.dtype}"
        for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]]
    digest = hashlib.sha256("\n".join(lines).encode()).hexdigest()[:16]
    assert (len(lines), digest) == PARENT[name][0], lines


@pytest.mark.parametrize("name", ROWS)
def test_the_cache_spec_comes_from_the_layer_kinds(name):
    fam = MODEL_FAMILIES[name]
    assert fam.cache is decoder.cache_spec
    for cfg, want in zip((fam.tiny(), _published(name)), PARENT[name][1:]):
        assert fam.cache(cfg) == CacheSpec(*want)
        # what the spec counts is what the kinds say they keep
        kept = {array for kind in cfg.layer_types
                for array in cfg.mixers[kind].keeps}
        spec = fam.cache(cfg)
        assert kept == set(pool_arrays(spec) + state_arrays(spec))


@pytest.mark.parametrize("name", ROWS)
def test_the_rows_module_runs_its_layers_through_the_decoder(
        name, monkeypatch):
    """The module is ``Decoder`` under the family's name (the name is in
    every operation's path), with no loop or block of its own: each layer
    is one ``Block`` of the layer's kind, dense FFNs first."""
    fam = MODEL_FAMILIES[name]
    assert issubclass(fam.module, decoder.Decoder)
    assert "__call__" not in vars(fam.module)
    cfg = dataclasses.replace(fam.tiny(), remat=False)
    built = []
    real = decoder.Block.__call__

    def spy(self, x, cache=None):
        built.append((self.name, self.kind, self.dense))
        return real(self, x, cache)

    monkeypatch.setattr(decoder.Block, "__call__", spy)
    jax.eval_shape(fam.module(cfg).init, jax.random.PRNGKey(0),
                   jnp.zeros((1, 8), jnp.int32))
    assert built == [(f"layer_{i}", kind, i < cfg.n_dense_layers)
                     for i, kind in enumerate(cfg.layer_types)]


@pytest.mark.parametrize("name", sorted(PARENT_TEXT))
def test_the_norms_placement_leaves_the_older_rows_programs_alone(name):
    """``Block`` reads ``norm_output`` from the config; a config without
    the attribute (the seven older rows) lowers to the text it lowered to
    before the block knew the second placement: the full forward and the
    decode step, letter for letter.  (A K/V row's PREFILL text did change
    in that PR, by ``models/attention.py``: tests/test_paged_attention.py
    holds it to the old form's numbers.)"""
    from ray_tpu.llm.engine import jit_forward
    from ray_tpu.llm.kv_cache import init_pool, init_state

    fam = MODEL_FAMILIES[name]
    cfg = dataclasses.replace(fam.tiny(), remat=False)
    assert not hasattr(cfg, "norm_output")
    params = jax.eval_shape(lambda: fam.init(cfg, jax.random.PRNGKey(0)))
    full = jax.jit(lambda p, t: fam.module(cfg).apply(p, t)).lower(
        params, jax.ShapeDtypeStruct((2, 16), jnp.int32)).as_text()
    spec = fam.cache(cfg)
    kv = jax.eval_shape(lambda: init_pool(spec, 16, 4, cfg.dtype))
    state = jax.eval_shape(lambda: init_state(spec, 2, cfg.dtype))
    ints = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    args = [params, ints] + [kv[k] for k in pool_arrays(spec)] + [
        jax.ShapeDtypeStruct((2, 8), jnp.int32), ints]
    if state_arrays(spec):
        args += [state[k] for k in state_arrays(spec)] + [
            jax.ShapeDtypeStruct((2,), jnp.int32)]
    step = jit_forward(fam.module(cfg)).lower(*args).as_text()
    assert tuple(hashlib.sha256(text.encode()).hexdigest()[:16]
                 for text in (full, step)) == PARENT_TEXT[name]


def test_the_blocks_two_norm_placements_share_one_tree():
    """The output-side block (``norm_output``: ``x + norm(f(x))``) keeps
    the names the input-side block gives its norms, so the two differ in
    the program alone; and they do differ."""
    fam = MODEL_FAMILIES["olmohybrid"]
    cfg = dataclasses.replace(fam.tiny(), remat=False)
    assert cfg.norm_output

    class Before(type(cfg)):
        norm_output = False

    before = Before(**dataclasses.asdict(cfg))
    params = fam.init(cfg, jax.random.PRNGKey(0))
    tokens = jnp.arange(12, dtype=jnp.int32)[None]
    after = fam.module(cfg).apply(params, tokens)
    other = fam.module(before).apply(params, tokens)     # the same tree
    assert float(jnp.max(jnp.abs(after - other))) > 1e-3


def test_the_hooks_that_left_with_the_wrappers_fail_loud():
    """Two names the operator's fault tools patch named code that is now
    shared (``granite_faults`` ``rotary``: ``granite.attention``;
    ``lfm2_faults`` ``no_qk_norm``: ``lfm2.RMSNorm``).  Neither is left
    behind as a dead import: patching it raises AttributeError and does
    not inject nothing in silence (ROADMAP Design 5(c))."""
    import ray_tpu.models.granite as granite
    import ray_tpu.models.lfm2 as lfm2

    assert not hasattr(granite, "attention")
    assert not hasattr(lfm2, "RMSNorm")


def test_a_config_without_a_residual_kind_runs_the_one_stream_block():
    """The residual kind is read from the config as ``mixers`` and
    ``experts`` are: only ``xing40``'s has one (``decoder.Residual``), and
    its hooks are the names the fault tool patches
    (benchmark/tools/xing_faults.py: ``xing.HC``, ``xing.sinkhorn``,
    ``xing.RMSNorm``), which exist; the other rows' configs have no such
    attribute and no leaf of a map in their trees."""
    import ray_tpu.models.xing as xing

    for name, fam in MODEL_FAMILIES.items():
        kind = getattr(fam.tiny(), "residual", None)
        assert (kind is not None) == (name == "xing40"), name
    assert isinstance(xing.HC, decoder.Residual)
    assert xing.XingConfig.tiny().residual is xing.HC
    assert callable(xing.sinkhorn) and issubclass(
        xing.RMSNorm, decoder.nn.Module)
    assert not hasattr(xing, "MLAttention")     # Kimi-K2's, used from there


# ------------------------------------ the one position a prefill serves

@pytest.fixture(scope="module")
def prefill_of():
    """row -> (its tiny config in float32, ``run(n, **served)``: the
    engine's jitted forward of an ``n``-token prompt in a bucket of 16,
    over fresh pools)."""
    from ray_tpu.llm.engine import jit_forward
    from ray_tpu.llm.kv_cache import init_pool, init_state, pages_for

    page = 4

    @functools.lru_cache(maxsize=None)
    def build(name):
        fam = MODEL_FAMILIES[name]
        cfg = dataclasses.replace(fam.tiny(), dtype=jnp.float32,
                                  remat=False)
        spec = fam.cache(cfg)
        params = fam.init(cfg, jax.random.PRNGKey(7))
        fwd = jit_forward(fam.module(cfg))
        tokens = jax.random.randint(jax.random.PRNGKey(8), (1, BUCKET), 1,
                                    cfg.vocab_size, jnp.int32)

        def run(n, **served):
            real = jnp.arange(BUCKET) < n
            args = [params, jnp.where(real, tokens, 0),
                    *init_pool(spec, 16, page, cfg.dtype).values(),
                    jnp.arange(pages_for(cfg.max_seq, page),
                               dtype=jnp.int32)[None] % 16,
                    jnp.where(real, jnp.arange(BUCKET), -1)[None]]
            if spec.state_layers:
                args += [*init_state(spec, 2, cfg.dtype).values(),
                         jnp.ones(1, jnp.int32)]
            return fwd(*args, **served)

        return cfg, run

    return build


BUCKET = 16


@pytest.mark.parametrize("n", [BUCKET, BUCKET - 5],
                         ids=["bucket_end", "inside_the_padding"])
@pytest.mark.parametrize("name", ["gpt2"] + ROWS)
def test_a_prefill_told_its_served_position_returns_that_row(
        prefill_of, name, n):
    """``last`` cuts the hidden state to one position AFTER the last
    block: the logits [1, 1, V] are the row the same prefill returns at
    that index among all of them, and every cache and state, and what the
    layers sowed, is written bit for bit as without it."""
    cfg, run = prefill_of(name)
    all_rows, *kept = run(n)
    assert all_rows.shape == (1, BUCKET, cfg.vocab_size)
    one, *kept_one = run(n, last=jnp.asarray([n - 1], jnp.int32))
    assert one.shape == (1, 1, cfg.vocab_size) and one.dtype == jnp.float32
    np.testing.assert_allclose(one[0, 0], all_rows[0, n - 1], rtol=0,
                               atol=1e-5)
    assert len(kept) == len(kept_one) > 0
    for a, b in zip(kept, kept_one):
        np.testing.assert_array_equal(a, b)
    assert any(np.asarray(a).any() for a in kept)      # something written
