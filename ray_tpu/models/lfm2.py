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

The layer loop, the block, the attention wrapper and the FFN are
``models/decoder.py``'s; this file is the config, the mixer, its kind
(``MIXERS``: the names in the tree, what a layer keeps) and the init.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

from .decoder import (Attention, Decoder, Mixer, attention_kind,
                      decoder_rules, next_token_loss)
from .layers import init_by_leaf, slot_conv

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

    # What ``models/decoder.py`` reads besides the fields: the kinds, the
    # experts of the layers after the dense ones, the tied head.
    tied_head = True

    @property
    def mixers(self):
        return MIXERS

    @property
    def experts(self):
        """``ops/moe.py MoEMLP``'s arguments."""
        return dict(d_ff=self.moe_d_ff, num_experts=self.n_experts,
                    top_k=self.experts_per_token, scoring="sigmoid",
                    select_bias=True, norm_eps=ROUTE_NORM_EPS,
                    first_expert=self.first_expert,
                    held_experts=self.held_experts)


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


class Lfm2(Decoder):
    """``models/decoder.py Decoder`` over an Lfm2Config, the contract of
    Granite's with a state pool of one array: ``k_pages`` / ``v_pages``
    [attention layers, pages, page, h_kv*d]; ``conv`` [short-conv layers,
    slots, taps-1, d] and ``slots`` [B]; all carried whole through the
    layers."""


MIXERS = {
    CONV: Mixer(ShortConvMixer, "conv", ("conv",), lambda cfg: {
        "conv_shape": (cfg.conv_taps - 1, cfg.d_model)}),
    # RoPE, after an RMSNorm over each head's own q and k
    ATTENTION: attention_kind(functools.partial(Attention, qk_norm="head")),
}


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


# (the source balances its experts through ``expert_bias``, not a loss)
lfm2_loss_fn = functools.partial(next_token_loss, Lfm2)


def lfm2_partition_rules():
    """``models/decoder.py decoder_rules`` after the mixer's own: its
    projections a column- then a row-parallel pair, the taps whole."""
    return decoder_rules(
        (r"in_proj/kernel$", PS("fsdp", "tensor")),
        (r"out_proj/kernel$", PS("tensor", "fsdp")),
        ("conv_w$", PS()))
