"""ONE decoder for the serving families (Llama and OLMoE, Granite 4.0-H,
LFM2-MoE, Kimi-K2, Kimi-Linear, Xing4.0, Olmo-Hybrid, Command A+): a
layer is a MIXER kind plus an FFN kind, joined by a RESIDUAL kind, and an
architecture is a config and
the kind it adds (its module, its scan and step, its init and the rules of
its own leaves stay in its file).

``Decoder(cfg)`` holds the only loop over layers outside ``gpt2.py``:
embed, one ``Block`` (``norm -> mixer -> + -> norm -> FFN -> +``) per
entry of ``cfg.layer_types``, ``norm_f``, the head.  What it reads of a
config, and nothing of a family's name:

- ``parallel_block`` (a config WITHOUT the attribute, every family but
  one, runs its two sublayers one after the other, the block above): true,
  ONE norm a layer, both sublayers read it and their branches are added
  once, ``y = norm(x)``; ``x + mixer(y) + ffn(y)`` (``models/cohere.py``);
- ``norm`` (a config WITHOUT the attribute: ``RMSNorm``): the module every
  norm of the block and ``norm_f`` are built from (``layers.LayerNorm``:
  the mean taken out);
- ``norm_output`` (a config WITHOUT the attribute, every family but one,
  norms a sublayer's INPUT, the block above): true, each norm lies on the
  sublayer's OUTPUT instead, ``x + norm(mixer(x))`` then ``h + norm(
  ffn(h))`` (``models/olmo_hybrid.py``), under the same names in the tree;

- ``residual`` (``Residual``; a config WITHOUT the attribute, every family
  but one, carries ONE stream ``x`` [B, T, d] and each sublayer adds its
  branch to it: the block above, program for program): what the layers
  hand one another, how it begins after ``embed`` and ends before
  ``norm_f``, and how each sublayer reads its input from it and writes its
  output back (``models/xing.py``: four streams a token, read, written and
  mixed through maps a sublayer computes from them);
- ``layer_types``: one entry a layer, each a key of ``mixers``;
- ``mixers``: ``{layer type: Mixer}``.  A ``Mixer`` says in one place the
  module that computes the kind, the names it has in the tree and what it
  keeps on the device between steps; the loop's hand-over of the cache
  and the row's ``CacheSpec`` (``cache_spec``) are both read from it;
- the FFN (``ffn``): the first ``n_dense_layers`` a dense SwiGLU of
  ``d_ff``, the others ``ops/moe.py MoEMLP`` with the arguments
  ``experts`` gives (None: no layer has experts) and, where
  ``shared_d_ff`` is there and not 0, a shared SwiGLU beside them
  (times ``shared_multiplier`` where a config has one: several shared
  experts averaged);
- ``tied_head`` (the head is the embedding), and Granite's multipliers
  where a config has them (``embedding_multiplier``,
  ``residual_multiplier``, ``logits_scaling``; Cohere's ``logit_scale``
  where it is not 1): a config without one gets no multiply in its
  program;
- ``vocab_size``, ``d_model``, ``rms_eps``, ``dtype``, ``remat``, ``mesh``.

With a cache (``kv_cache``: the pools' arrays, ``page_table``,
``window_table`` where there are window layers, ``slots`` where there is a
state pool; ``positions`` [B, T], < 0 padding) each layer
is handed the arrays ITS KIND keeps, whole, and its number among its own
kind, and what it returns is put back: every pool is carried whole
through the layers and updated where it lies (``llm/engine.py
jit_forward``).  Without one it is the full forward the trainer uses.

Grouped-query attention around the one core (``models/attention.py
attention``) is ``gqa``, a function that makes its submodules in the
caller's scope (Llama's layer is flat: ``layer_i/wq``), and ``Attention``,
the same as a module for a tree that has one (``layer_i/attn/wq``); the
families differ in RoPE or none, a QK-norm over the width before the
split into heads, over each head after it, or none, and the score's scale.

GPT-2 (``models/gpt2.py``) is NOT a row over this: LayerNorm with biases,
learned positions, one fused QKV split by head shard and the chunked loss
would make the shared block branch on its caller.
"""

from __future__ import annotations

import collections
import contextlib
import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

from ..parallel.sharding import with_logical_constraint as _constrain
from .attention import attention
from .layers import RMSNorm, _rope, served_position


@dataclass(frozen=True)
class CacheSpec:
    """What one sequence keeps on the device, by layer kind."""
    kv_layers: int                      # layers with K/V in the paged pool
    kv_heads: int                       # heads the pool stores (grouped)
    head_dim: int
    state_layers: int = 0               # layers with a recurrent state
    conv_shape: Tuple[int, ...] = ()    # one sequence, one layer (dtype)
    ssm_shape: Tuple[int, ...] = ()     # the same, float32; (): none
    latent_dim: int = 0                 # > 0: ONE latent row a position
    rope_dim: int = 0                   # (c_kv | k_pe), no K/V pools
    # A SECOND group of K/V layers, which keep ``window`` positions a
    # sequence however long it grows (a ring of pages of its own, with a
    # table of its own), beside the ``kv_layers`` that keep them all.
    window_layers: int = 0
    window: int = 0

    @property
    def row_width(self) -> int:
        """A latent row in the pool: whole tiles of 128 lanes."""
        return -(-(self.latent_dim + self.rope_dim) // 128) * 128


@dataclass(frozen=True)
class Mixer:
    """A mixer kind.  ``module(cfg, name=name)`` gives what computes it,
    called ``(y, cache)``: ``y`` [B, T, d] the normed activation, ``cache``
    None or this kind's arrays whole, ``layer`` (the layer's number among
    its kind), ``page_table`` or ``slots``, and ``positions``; it returns
    its output, or (output, the arrays updated: the one, or a tuple in
    the order of ``keeps``)."""
    module: Callable[..., Any]
    name: Optional[str]         # in the tree; None: the layer's own leaves
    keeps: Tuple[str, ...]      # the cache's arrays it reads and updates
    spec: Callable[[Any], Dict[str, Any]]   # cfg -> its CacheSpec fields
    norm: str = "mixer_norm"    # the norm before it, in the tree
    # The scope its residual add is filed under, where it has one (Llama's
    # lies inside ``attn.out``: re-entered by name, the path is the same).
    residual_scope: Optional[str] = None

    @property
    def index(self) -> str:
        """What its arrays are indexed through: pages by their group's
        table, a recurrent state by the row's slot."""
        if not self.keeps[0].endswith("_pages"):
            return "slots"
        return "window_table" if self.keeps[0].startswith("window_") \
            else "page_table"


@dataclass(frozen=True)
class Residual:
    """A RESIDUAL kind: what the layers hand one another where that is
    not one stream ``x`` [B, T, d] with ``x + branch`` after every
    sublayer (the kind of a config that has no ``residual``).  The state
    begins after ``embed`` (``begin(cfg, x)``) and ends before ``norm_f``
    (``end(cfg, state)`` -> [B, T, d]); each of a layer's two sublayers
    READS its input from the state through a module of its own in the
    layer's tree (``read(cfg, name=...)(state, live)`` -> (the input
    [B, T, d], what its write needs); ``live`` [B, T] bool or None: the
    rows that are no padding) and WRITES its output back (``write(cfg,
    state, what read gave, branch)`` -> the state)."""
    begin: Callable[..., Any]
    read: Callable[..., Any]
    write: Callable[..., Any]
    end: Callable[..., Any]
    axes: Tuple[str, ...]               # the state's logical axes
    describe: Callable[[Any], Dict[str, int]]   # cfg -> what stats() say
    names: Tuple[str, str] = ("attn_hc", "mlp_hc")  # the two read modules


def residual_counters(intermediates):
    """The largest of what the read modules of a residual kind sowed
    (``residual``: each a vector of one shape, float32) over the layers
    and sublayers: what the engine fetches beside the logits.  None for a
    model of one stream."""
    found = []

    def walk(node) -> None:
        for key, child in node.items():
            if key == "residual" and isinstance(child, tuple):
                found.extend(child)
            elif hasattr(child, "items"):
                walk(child)
    walk(intermediates)
    return jnp.max(jnp.stack(found), axis=0) if found else None


def cache_spec(cfg) -> CacheSpec:
    """The ``CacheSpec`` of a config, from its layers' kinds."""
    fields = {"kv_layers": 0, "kv_heads": 0, "head_dim": 0,
              "state_layers": 0, "window_layers": 0}
    counts = {"page_table": "kv_layers", "window_table": "window_layers",
              "slots": "state_layers"}
    for kind, mixer in cfg.mixers.items():
        fields[counts[mixer.index]] += cfg.layer_types.count(kind)
        fields.update(mixer.spec(cfg))
    return CacheSpec(**fields)


def _dense(cfg):
    """``nn.Dense`` as every projection here is made: no bias, normal(0,
    0.02)."""
    return functools.partial(nn.Dense, use_bias=False, dtype=cfg.dtype,
                             kernel_init=nn.initializers.normal(0.02))


def _scope(name: Optional[str]):
    return jax.named_scope(name) if name else contextlib.nullcontext()


# ------------------------------------------------ grouped-query attention

def _head_dim(cfg) -> int:
    """A head's width: the config's own where it states one (Command A+:
    128 heads of 128 over a model 4,096 wide), else ``d_model / n_head``."""
    return getattr(cfg, "head_dim", cfg.d_model // cfg.n_head)


def gqa(cfg, y, cache=None, *, rope: bool = True,
        qk_norm: Optional[str] = None, scale: Optional[float] = None,
        interleaved: bool = False, window: Optional[int] = None,
        scope: Optional[str] = None):
    """y [B, T, d] -> (out [B, T, d], the K/V pool updated or None), the
    submodules made in the CALLER's scope.  ``qk_norm``: ``"width"`` (an
    RMSNorm over the whole q and k before the split into heads: OLMoE),
    ``"head"`` (over each head's own: LFM2) or None; ``rope`` False: no
    position encoding (Granite); ``interleaved``: RoPE turns adjacent
    pairs; ``scale``: the score's, None 1/sqrt(d); ``window``: a sliding
    window (``models/attention.py attention``: its cache is the SECOND
    group's, ``window_k_pages`` / ``window_v_pages`` through
    ``window_table``); ``scope``: what a capture files the cached core
    under.  The cache stores the GROUPED heads (after RoPE); the full
    forward repeats them to the query heads."""
    h, hk, dh = cfg.n_head, cfg.n_kv_head, _head_dim(cfg)
    b, t = y.shape[0], y.shape[1]
    positions = cache["positions"] if cache is not None else None
    dense = _dense(cfg)
    norm = functools.partial(RMSNorm, cfg.rms_eps, cfg.dtype)
    # Scope names as in models/gpt2.py (metadata only).
    with jax.named_scope("attn.qkv"):
        q, k, v = (dense(heads * dh, name=name)(y)
                   for name, heads in (("wq", h), ("wk", hk), ("wv", hk)))
        if qk_norm == "width":
            with jax.named_scope("attn.qk_norm"):
                q, k = norm(name="q_norm")(q), norm(name="k_norm")(k)
        q, k, v = (z.reshape(b, t, -1, dh) for z in (q, k, v))
        if qk_norm == "head":
            with jax.named_scope("attn.qk_norm"):
                q, k = norm(name="q_norm")(q), norm(name="k_norm")(k)
        if rope:    # (the pairing is said only where it is not the default)
            pairs = {"interleaved": True} if interleaved else {}
            q, k = (_rope(z, cfg.rope_theta, positions, **pairs)
                    for z in (q, k))
    kind = {}           # what only a layer with a window or a scope says
    if window is not None:
        kind["window"] = window
        if cache is not None:       # the second group's arrays and table
            cache = {**cache, "k_pages": cache["window_k_pages"],
                     "v_pages": cache["window_v_pages"],
                     "page_table": cache["window_table"]}
    if scope is not None:
        kind["scope"] = scope
    att, kept = attention(cfg, q, k, v, cache, scale=scale, **kind)
    with jax.named_scope("attn.out"):
        out = dense(cfg.d_model, name="wo")(att.reshape(b, t, h * dh))
    return out, kept


class Attention(nn.Module):
    """``gqa`` as a module, for a tree that has one (``layer_i/attn/``)."""
    cfg: Any
    rope: bool = True
    qk_norm: Optional[str] = None
    scale: Optional[float] = None
    interleaved: bool = False
    window: Optional[int] = None
    core_scope: Optional[str] = None    # ``gqa``'s ``scope``

    @nn.compact
    def __call__(self, y, cache=None):
        return gqa(self.cfg, y, cache, rope=self.rope,
                   qk_norm=self.qk_norm, scale=self.scale,
                   interleaved=self.interleaved, window=self.window,
                   scope=self.core_scope)


def attention_kind(module, name: Optional[str] = "attn", **names) -> Mixer:
    """The kind of a grouped-query attention layer: K and V in the paged
    pool at the grouped heads' width."""
    return Mixer(module, name, ("k_pages", "v_pages"),
                 lambda cfg: {"kv_heads": cfg.n_kv_head,
                              "head_dim": _head_dim(cfg)},
                 **names)


def window_kind(module, name: Optional[str] = "attn", **names) -> Mixer:
    """The kind of a grouped-query attention layer with a sliding window
    (``cfg.sliding_window`` positions): K and V in the SECOND group's
    pages, a ring of that many positions a sequence."""
    return Mixer(module, name, ("window_k_pages", "window_v_pages"),
                 lambda cfg: {"kv_heads": cfg.n_kv_head,
                              "head_dim": _head_dim(cfg),
                              "window": cfg.sliding_window},
                 **names)


# ---------------------------------------------------------------- the FFN

def _swiglu(cfg, y, width: int, names):
    dense = _dense(cfg)
    gate, up = (dense(width, name=name)(y) for name in names[:2])
    z = _constrain(nn.silu(gate) * up, ("batch", "seq", "mlp"), cfg.mesh)
    return dense(cfg.d_model, name=names[2])(z)


def _plus(cfg, x, branch):
    """``x`` + the config's residual multiplier x ``branch``."""
    mult = getattr(cfg, "residual_multiplier", 1.0)
    if mult != 1.0:
        branch = mult * branch
    return x + branch.astype(x.dtype)


def ffn(cfg, y, dense: bool, positions, write):
    """``write(ffn(y))`` inside the calling block (the submodules are the
    caller's; ``write`` puts a branch back into the residual state): a
    dense SwiGLU of ``d_ff``, or the routed experts and, where the config
    has one, the shared expert beside them.  ``positions`` [B, T] (< 0:
    padding, kept from the experts) or None."""
    with jax.named_scope("mlp"):
        if dense:
            # (filed apart where the other layers have experts)
            with _scope("mlp.dense" if cfg.experts else None):
                down = _swiglu(cfg, y, cfg.d_ff,
                               ("w_gate", "w_up", "w_down"))
        else:
            from ..ops.moe import MoEMLP

            down = MoEMLP(d_model=cfg.d_model, gated=True, act=nn.silu,
                          dtype=cfg.dtype, name="moe", **cfg.experts)(
                y, None if positions is None else positions >= 0)
            if getattr(cfg, "shared_d_ff", 0):
                with jax.named_scope("moe.shared"):
                    shared = _swiglu(
                        cfg, y, cfg.shared_d_ff,
                        ("shared_gate", "shared_up", "shared_down"))
                    # (several shared experts AVERAGED are one SwiGLU of
                    # their summed width times 1 / their number)
                    mult = getattr(cfg, "shared_multiplier", 1.0)
                    if mult != 1.0:
                        shared = shared * jnp.asarray(mult, shared.dtype)
                    down = down + shared
        return write(down)


# ------------------------------------------------- the block, the decoder

class Block(nn.Module):
    cfg: Any
    kind: str           # of ``cfg.mixers``
    dense: bool         # its FFN: the dense SwiGLU, or the experts

    @nn.compact
    def __call__(self, x, cache=None):
        """``cache`` is this layer's kind's; returns x, or (x, what the
        mixer updated)."""
        cfg = self.cfg
        mixer = cfg.mixers[self.kind]
        res = getattr(cfg, "residual", None)    # None: ONE stream
        positions = cache["positions"] if cache is not None else None

        def read(x, sublayer: int):
            """A sublayer's input, and what its write needs."""
            if res is None:
                return x, None
            return res.read(cfg, name=res.names[sublayer])(
                x, None if positions is None else positions >= 0)

        def write(x, maps, branch):
            if res is None:
                return _plus(cfg, x, branch)
            return res.write(cfg, x, maps, branch)

        Norm = getattr(cfg, "norm", RMSNorm)    # the module, as data
        if getattr(cfg, "parallel_block", False):
            # ONE norm, read by both sublayers; their branches added once:
            # ``x + mixer(y) + ffn(y)``, ``y = norm(x)``.
            if res is not None or getattr(cfg, "norm_output", False):
                raise ValueError("a parallel block has one stream and "
                                 "norms its input")
            y = Norm(cfg.rms_eps, cfg.dtype, name=mixer.norm)(x)
            m = mixer.module(cfg, name=mixer.name)(y, cache)
            m, kept = m if isinstance(m, tuple) else (m, None)
            x = ffn(cfg, y, self.dense, positions,
                    lambda down: _plus(cfg, x, m + down))
            return x if cache is None else (x, kept)
        # Where a sublayer's norm lies: before it (``x + f(norm(x))``), or,
        # for a config that says ``norm_output``, on what it gives (``x +
        # norm(f(x))``: the OLMo 2 / OLMo 3 block).
        after = getattr(cfg, "norm_output", False)
        norm = Norm(cfg.rms_eps, cfg.dtype, name=mixer.norm)
        u, maps = read(x, 0)
        m = mixer.module(cfg, name=mixer.name)(u if after else norm(u),
                                               cache)
        m, kept = m if isinstance(m, tuple) else (m, None)
        with _scope(mixer.residual_scope):
            x = write(x, maps, norm(m) if after else m)
        norm = Norm(cfg.rms_eps, cfg.dtype, name="mlp_norm")
        u, maps = read(x, 1)
        back = functools.partial(write, x, maps)
        x = ffn(cfg, u if after else norm(u), self.dense, positions,
                (lambda down: back(norm(down))) if after else back)
        return x if cache is None else (x, kept)


class Decoder(nn.Module):
    cfg: Any

    @nn.compact
    def __call__(self, tokens, kv_cache=None, positions=None, last=None):
        """Full forward (kv_cache=None) or a step against the caches (the
        contract of GPT2.__call__, ``llm/kv_cache.py``): returns logits,
        or (logits, the cache updated).  ``last`` (int32 [B], an index
        within T; None: every position) is the ONE position of each row
        the caller serves: every layer still runs, and writes its cache,
        over all T, and what follows the last block (the residual kind's
        end, ``norm_f``, the head) runs on that position alone: logits
        [B, 1, V]."""
        cfg = self.cfg
        cached = kv_cache is not None
        init = nn.initializers.normal(0.02)
        emb = self.param("embed", init, (cfg.vocab_size, cfg.d_model),
                         jnp.float32)
        with jax.named_scope("embed"):
            x = emb.astype(cfg.dtype)[tokens]
            if hasattr(cfg, "embedding_multiplier"):
                x = x * jnp.asarray(cfg.embedding_multiplier, cfg.dtype)
            x = _constrain(x, ("batch", "seq", "embed"), cfg.mesh)
        res = getattr(cfg, "residual", None)
        axes = ("batch", "seq", "embed")
        if res is not None:     # the state the layers hand on
            axes = res.axes
            x = _constrain(res.begin(cfg, x), axes, cfg.mesh)
        block = Block
        if cfg.remat and not cached:
            block = nn.remat(Block, prevent_cse=False)
        new = dict(kv_cache) if cached else None
        seen = collections.Counter()
        for i, kind in enumerate(cfg.layer_types):
            blk = block(cfg, kind, i < cfg.n_dense_layers,
                        name=f"layer_{i}")
            if not cached:
                x = blk(x)
            else:       # ONE pool of each kind through every layer
                mixer = cfg.mixers[kind]
                x, kept = blk(x, cache={
                    **{name: new[name] for name in mixer.keeps},
                    "layer": seen[kind], mixer.index: new[mixer.index],
                    "positions": positions})
                new.update(zip(mixer.keeps, kept if len(mixer.keeps) > 1
                               else (kept,)))
            seen[kind] += 1
            x = _constrain(x, axes, cfg.mesh)
        if last is not None:
            x = served_position(x, last)
        if res is not None:
            x = res.end(cfg, x)
        x = getattr(cfg, "norm", RMSNorm)(cfg.rms_eps, cfg.dtype,
                                           name="norm_f")(x)
        with jax.named_scope("lm_head"):
            if getattr(cfg, "tied_head", False):
                logits = jnp.einsum("btd,vd->btv", x, emb.astype(cfg.dtype),
                                    preferred_element_type=jnp.float32)
            else:
                head = self.param("lm_head", init,
                                  (cfg.d_model, cfg.vocab_size), jnp.float32)
                logits = jnp.einsum("btd,dv->btv", x,
                                    head.astype(cfg.dtype),
                                    preferred_element_type=jnp.float32)
            if hasattr(cfg, "logits_scaling"):
                logits = logits / cfg.logits_scaling
            if getattr(cfg, "logit_scale", 1.0) != 1.0:
                logits = logits * cfg.logit_scale
            logits = _constrain(logits, ("batch", "seq", "vocab"), cfg.mesh)
        return (logits, new) if cached else logits


# ------------------------------------------------------------ loss, rules

def _next_token_xent(logits, targets):
    with jax.named_scope("loss"):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(logp, targets[..., None],
                                 axis=-1)[..., 0]
        return -jnp.mean(ll)


def next_token_loss(model, cfg, params, batch):
    """Mean next-token cross entropy of ``model(cfg)`` on
    ``batch["tokens"]`` [B, T + 1]."""
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    return _next_token_xent(model(cfg).apply(params, inputs), targets)


def decoder_rules(*own):
    """fsdp + tensor partition rules (``match_partition_rules`` form: the
    first match wins; see ``gpt2_partition_rules``) for a decoder's tree:
    a family's rules for its OWN leaves, then what every family has: the
    embedding and the head; the experts, every one on every chip, their
    matrices sharded over fsdp x tensor on their ``d`` and ``f``
    dimensions (experts over an ``expert`` mesh axis is ROADMAP Reach
    5's); attention's and the SwiGLUs' projections as column- then
    row-parallel pairs; the norms' scales and the selection bias whole."""
    col, row = PS("fsdp", "tensor"), PS("tensor", "fsdp")
    return tuple(own) + (
        ("embed$", PS("tensor", "fsdp")),
        ("lm_head$", col),
        (r"moe/(w_gate|w_up)$", PS(None, "fsdp", "tensor")),
        (r"moe/w_down$", PS(None, "tensor", "fsdp")),
        (r"moe/router$", PS("fsdp", None)),
        (r"(w[qkv]|w_gate|w_up|shared_gate|shared_up)/kernel$", col),
        (r"(wo|w_down|shared_down)/kernel$", row),
        (r"(scale|expert_bias)$", PS()),
    )
