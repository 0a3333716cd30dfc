"""LFM2 mixture-of-experts family (HF ``model_type`` lfm2_moe; Liquid AI's
LFM2-24B-A2B) — layers of two kinds in one model: gated short-convolution
mixers and, among every few, grouped-query attention with a per-head
RMSNorm on q and k before RoPE; the FFN a dense SwiGLU in the first
``n_dense_layers`` and sparse experts after them (``ops/moe.py``: sigmoid
scores, a per-expert selection bias).  No bias anywhere; the output head
is the embedding, tied.

Layer ``l``: ``h = x + mixer_l(RMSNorm(x))``; ``y = h + ffn_l(RMSNorm(h))``.
After the last layer one more RMSNorm, then the head.

The short-conv mixer on ``u`` [T, d]: ``[B | C | X] = u W_in`` (each ``d``
wide, in this order); ``z = B * X``; ``c_t = sum_j w[j] * z_{t-2+j}`` (a
depthwise causal conv of ``conv_taps`` = 3 taps, ``z`` zero before position
0; ``conv_w`` is held [taps, d], the source's [d, 1, taps] transposed);
``out = (C * c) W_out``.  No activation function.  Between steps a
sequence keeps the last two ``z``: its whole recurrent state, ``[taps - 1,
d]`` in the model's dtype (8 KB a layer at 2048 wide), in its slot of the
state pool's ``conv`` (``llm/kv_cache.py``; no ``ssm`` array: the model
has no state-space state).  The conv over that window is
``models/layers.py slot_conv``, the one Granite's mixer calls.

The sparse FFN: ``r = x W_g`` (float32); ``s = sigmoid(r)``; the
``experts_per_token`` largest of ``s + expert_bias`` are chosen; their
weights are ``s_i / (sum_chosen s + 1e-6)``, the bias not in them; no
shared expert.  ``expert_bias`` takes no gradient (the source moves it by
the experts' load outside autograd; here it stays as init drew it).

With a cache the contract is Granite's (``models/granite.py``): the K/V
pool for the attention layers, the state pool for the mixers, each row's
slot read and written where it lies, both carried whole through the
layers; a position < 0 is padding and a slot index outside the pool a
padded row.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..parallel.sharding import with_logical_constraint as _constrain
from .attention import attention
from .layers import RMSNorm, _rope, init_by_leaf, slot_conv
from .llama import _next_token_xent

CONV, ATTENTION = "conv", "full_attention"
ROUTE_NORM_EPS = 1e-6       # in the sum of the chosen experts' scores
EXPERT_BIAS_STD = 0.02      # how init draws ``expert_bias``


@dataclass(frozen=True)
class Lfm2Config:
    """LiquidAI/LFM2-24B-A2B as published (the defaults): 40 layers,
    attention at 2, 6, ..., 38; 2048 wide; 32 query and 8 K/V heads of
    64; layers 0-1 a dense SwiGLU of 11776, layers 2-39 top-4 of 64
    experts of width 1536."""
    vocab_size: int = 65536
    layer_types: Tuple[str, ...] = tuple(
        ATTENTION if i % 4 == 2 else CONV for i in range(40))
    d_model: int = 2048
    n_head: int = 32
    n_kv_head: int = 8
    d_ff: int = 11776                   # the dense layers' width
    n_dense_layers: int = 2
    moe_d_ff: int = 1536                # one expert's width
    n_experts: int = 64                 # what the router scores
    experts_per_token: int = 4
    # The share of the experts held here (ops/moe.py); None: all.
    first_expert: int = 0
    held_experts: Optional[int] = None
    conv_taps: int = 3
    rope_theta: float = 1000000.0
    max_seq: int = 128000
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    attn_impl: str = "dense"
    remat: bool = True
    mesh: Any = None

    def __post_init__(self):
        bad = set(self.layer_types) - {CONV, ATTENTION}
        if bad:
            raise ValueError(f"layer_types holds {sorted(bad)}")

    @staticmethod
    def tiny(**overrides) -> "Lfm2Config":
        """The shape at a test's size: [conv, conv, attention, conv,
        conv], the first two layers dense; 64 wide, 4 query and 2 K/V
        heads of 16, a dense FFN of 96, top-2 of 8 experts of width 32,
        3 taps."""
        return Lfm2Config(**{**dict(
            vocab_size=256, layer_types=(CONV, CONV, ATTENTION, CONV, CONV),
            d_model=64, n_head=4, n_kv_head=2, d_ff=96, moe_d_ff=32,
            n_experts=8, experts_per_token=2, max_seq=128,
            dtype=jnp.float32, param_dtype=jnp.float32), **overrides})

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head

    @property
    def n_moe_layers(self) -> int:
        return max(self.n_layer - self.n_dense_layers, 0)

    def layers_of(self, kind: str) -> int:
        return sum(t == kind for t in self.layer_types)

    def mixer_params(self) -> int:
        """One short-conv layer's mixer (``in_proj``, ``out_proj`` and
        the taps), in parameters."""
        return 4 * self.d_model * self.d_model \
            + self.conv_taps * self.d_model

    def flops_per_token(self) -> float:
        """Training FLOPs a token: 6 x the matmul parameters a token
        passes through (its k experts, not all of them)."""
        attn = 2 * self.d_model * (self.n_head + self.n_kv_head) \
            * self.head_dim
        sparse = 3 * self.d_model * self.moe_d_ff * self.experts_per_token \
            + self.d_model * self.n_experts
        dense = min(self.n_dense_layers, self.n_layer)
        n = self.vocab_size * self.d_model \
            + dense * 3 * self.d_model * self.d_ff \
            + self.n_moe_layers * sparse \
            + self.layers_of(CONV) * self.mixer_params() \
            + self.layers_of(ATTENTION) * attn
        return 6.0 * n


class ShortConvMixer(nn.Module):
    cfg: Lfm2Config

    @nn.compact
    def __call__(self, u, cache=None):
        """u [B, T, d] -> [B, T, d]; with ``cache`` ({"conv", "layer",
        "slots", "positions"}: the WHOLE state pool and this mixer's
        layer in it) returns (out, the pool with each row's slot
        updated)."""
        cfg = self.cfg
        init = nn.initializers.normal(0.02)
        with jax.named_scope("conv.in_proj"):
            proj = nn.Dense(3 * cfg.d_model, use_bias=False,
                            dtype=cfg.dtype, kernel_init=init,
                            name="in_proj")(u)
            b_gate, c_gate, x = jnp.split(proj, 3, axis=-1)
        conv_w = self.param("conv_w", init, (cfg.conv_taps, cfg.d_model),
                            jnp.float32)
        with jax.named_scope("conv.gate"):
            z = b_gate * x
        with jax.named_scope("conv.window"):
            window = None
            if cache is not None:
                pos = cache["positions"]
                window = (cache["conv"], cache["layer"], cache["slots"],
                          pos[:, 0] == 0, pos >= 0)
            c, pool = slot_conv(z, conv_w.astype(jnp.float32), window)
        with jax.named_scope("conv.gate"):
            y = (c_gate.astype(jnp.float32) * c).astype(cfg.dtype)
        with jax.named_scope("conv.out_proj"):
            out = nn.Dense(cfg.d_model, use_bias=False, dtype=cfg.dtype,
                           kernel_init=init, name="out_proj")(y)
        return out if cache is None else (out, pool)


class Lfm2Attention(nn.Module):
    cfg: Lfm2Config

    @nn.compact
    def __call__(self, y, cache=None):
        cfg = self.cfg
        h, hk, dh = cfg.n_head, cfg.n_kv_head, cfg.head_dim
        b, t = y.shape[0], y.shape[1]
        init = nn.initializers.normal(0.02)
        positions = cache["positions"] if cache is not None else None
        with jax.named_scope("attn.qkv"):
            q, k, v = (nn.Dense(heads * dh, use_bias=False, dtype=cfg.dtype,
                                kernel_init=init, name=name)(y)
                       .reshape(b, t, heads, dh)
                       for name, heads in (("wq", h), ("wk", hk),
                                           ("wv", hk)))
            with jax.named_scope("attn.qk_norm"):   # over each head's own
                q = RMSNorm(cfg.rms_eps, cfg.dtype, name="q_norm")(q)
                k = RMSNorm(cfg.rms_eps, cfg.dtype, name="k_norm")(k)
            q = _rope(q, cfg.rope_theta, positions)
            k = _rope(k, cfg.rope_theta, positions)
        att, new_cache = attention(cfg, q, k, v, cache)
        with jax.named_scope("attn.out"):
            out = nn.Dense(cfg.d_model, use_bias=False, dtype=cfg.dtype,
                           kernel_init=init,
                           name="wo")(att.reshape(b, t, h * dh))
        return out, new_cache


class Lfm2Block(nn.Module):
    cfg: Lfm2Config
    kind: str
    dense: bool

    @nn.compact
    def __call__(self, x, cache=None):
        """``cache`` is the attention core's (an attention layer) or the
        mixer's (a short-conv layer); returns x, or (x, what the layer
        updated)."""
        cfg = self.cfg
        y = RMSNorm(cfg.rms_eps, cfg.dtype, name="mixer_norm")(x)
        if self.kind == ATTENTION:
            m, new = Lfm2Attention(cfg, name="attn")(y, cache)
        else:
            m = ShortConvMixer(cfg, name="conv")(y, cache)
            new = None
            if cache is not None:
                m, new = m
        x = x + m.astype(x.dtype)
        y = RMSNorm(cfg.rms_eps, cfg.dtype, name="mlp_norm")(x)
        positions = cache["positions"] if cache is not None else None
        with jax.named_scope("mlp"):
            if self.dense:
                with jax.named_scope("mlp.dense"):
                    init = nn.initializers.normal(0.02)
                    gate, up = (nn.Dense(cfg.d_ff, use_bias=False,
                                         dtype=cfg.dtype, kernel_init=init,
                                         name=name)(y)
                                for name in ("w_gate", "w_up"))
                    z = _constrain(nn.silu(gate) * up,
                                   ("batch", "seq", "mlp"), cfg.mesh)
                    down = nn.Dense(cfg.d_model, use_bias=False,
                                    dtype=cfg.dtype, kernel_init=init,
                                    name="w_down")(z)
            else:
                from ..ops.moe import MoEMLP

                down = MoEMLP(
                    d_model=cfg.d_model, d_ff=cfg.moe_d_ff,
                    num_experts=cfg.n_experts, top_k=cfg.experts_per_token,
                    gated=True, norm_topk_prob=True, scoring="sigmoid",
                    select_bias=True, norm_eps=ROUTE_NORM_EPS,
                    act=nn.silu, dtype=cfg.dtype,
                    first_expert=cfg.first_expert,
                    held_experts=cfg.held_experts, name="moe")(
                        y, None if positions is None else positions >= 0)
            x = x + down.astype(x.dtype)
        return x if cache is None else (x, new)


class Lfm2(nn.Module):
    cfg: Lfm2Config

    @nn.compact
    def __call__(self, tokens, kv_cache=None, positions=None):
        """Full forward (kv_cache=None) or a step against the caches, the
        contract of Granite.__call__ with a state pool of one array:
        ``k_pages`` / ``v_pages`` [attention layers, pages, page,
        h_kv*d]; ``conv`` [short-conv layers, slots, taps-1, d] and
        ``slots`` [B]; all carried whole through the layers.  Returns
        (logits, the cache updated)."""
        cfg = self.cfg
        cached = kv_cache is not None
        emb = self.param("embed", nn.initializers.normal(0.02),
                         (cfg.vocab_size, cfg.d_model), jnp.float32)
        with jax.named_scope("embed"):
            x = emb.astype(cfg.dtype)[tokens]
            x = _constrain(x, ("batch", "seq", "embed"), cfg.mesh)
        block = Lfm2Block
        if cfg.remat and not cached:
            block = nn.remat(Lfm2Block, prevent_cse=False)
        if cached:
            new = dict(kv_cache)
        seen = {CONV: 0, ATTENTION: 0}
        for i, kind in enumerate(cfg.layer_types):
            blk = block(cfg, kind, i < cfg.n_dense_layers,
                        name=f"layer_{i}")
            if not cached:
                x = blk(x)
            elif kind == ATTENTION:
                x, (new["k_pages"], new["v_pages"]) = blk(x, cache={
                    "k_pages": new["k_pages"], "v_pages": new["v_pages"],
                    "layer": seen[kind], "page_table": new["page_table"],
                    "positions": positions})
            else:
                x, new["conv"] = blk(x, cache={
                    "conv": new["conv"], "layer": seen[kind],
                    "slots": new["slots"], "positions": positions})
            seen[kind] += 1
            x = _constrain(x, ("batch", "seq", "embed"), cfg.mesh)
        x = RMSNorm(cfg.rms_eps, cfg.dtype, name="norm_f")(x)
        with jax.named_scope("lm_head"):        # tied to the embedding
            logits = jnp.einsum("btd,vd->btv", x, emb.astype(cfg.dtype),
                                preferred_element_type=jnp.float32)
            logits = _constrain(logits, ("batch", "seq", "vocab"), cfg.mesh)
        return (logits, new) if cached else logits


# ------------------------------------------------------ init, loss, rules

def _special_leaf(cfg: Lfm2Config, name: str, key, shape):
    """The leaves that are not normal(0, 0.02) or a norm's ones: None
    for the others."""
    leaf = name.rsplit("/", 1)[-1]
    dtype = jnp.dtype(cfg.param_dtype)
    if leaf == "conv_w":    # PyTorch's depthwise default
        bound = cfg.conv_taps ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound,
                                  bound).astype(dtype)
    if leaf == "expert_bias":
        return EXPERT_BIAS_STD * jax.random.normal(key, shape, jnp.float32)
    return None


def lfm2_init(cfg: Lfm2Config, rng):
    """The weights from the seed, leaf by leaf as ``llama_init`` makes
    them (shapes by ``eval_shape``; each leaf float32 from a key folded
    from its path, cast to ``cfg.param_dtype``): matrices and the tied
    embedding normal(0, 0.02), norm scales 1, the conv's taps uniform in
    +-1/sqrt(taps), and ``expert_bias`` normal(0, ``EXPERT_BIAS_STD``) in
    float32: NOT zero, where its absence could not show.  At 64 experts
    the sigmoid scores around a row's 4th and 5th largest lie ~0.015
    apart: a bias of 0.02 changes about half the rows' choice of experts
    and leaves a batch of 16 rows on ~40 experts a layer, as an unbiased
    router does; at 0.1 it changed nearly every row's and crowded the
    batch onto ~30 (read on the chip, PR 33; PERF.md section 6)."""
    return init_by_leaf(Lfm2, cfg, rng,
                        functools.partial(_special_leaf, cfg))


def lfm2_loss_fn(cfg: Lfm2Config, params, batch):
    """Mean next-token cross entropy (the source balances its experts
    through ``expert_bias``, not through a loss)."""
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    return _next_token_xent(Lfm2(cfg).apply(params, inputs), targets)


def lfm2_partition_rules():
    """fsdp + tensor rules for LFM2 trees: the mixer's projections and
    the dense FFN as column- then row-parallel pairs, the experts as
    OLMoE's, every expert on every chip."""
    from jax.sharding import PartitionSpec as PS

    return (
        ("embed$", PS("tensor", "fsdp")),
        (r"moe/(w_gate|w_up)$", PS(None, "fsdp", "tensor")),
        (r"moe/w_down$", PS(None, "tensor", "fsdp")),
        (r"moe/router$", PS("fsdp", None)),
        (r"(w[qkv]|in_proj|w_gate|w_up)/kernel$", PS("fsdp", "tensor")),
        (r"(wo|out_proj|w_down)/kernel$", PS("tensor", "fsdp")),
        (r"(scale|conv_w|expert_bias)$", PS()),
    )
