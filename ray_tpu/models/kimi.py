"""Kimi-K2 family (HF ``model_type`` kimi_k2; moonshotai's Kimi-K2.5, the
language model: its layer is the DeepSeek-V3 block) — multi-head LATENT
attention (MLA) over a cache whose row is one compressed vector and a
rotary part, a dense SwiGLU in the first ``n_dense_layers`` and after
them a shared expert beside experts routed by sigmoid scores and a
selection bias (``ops/moe.py``).  No bias anywhere; the head is untied.

Layer ``l``: ``h = x + attn_l(RMSNorm(x))``; ``y = h + ffn_l(RMSNorm(h))``.
After the last layer one more RMSNorm, then the head.

The attention on ``u`` [T, d], with ``H`` heads, ``r_q`` / ``r_kv`` the
two ranks, ``d_n`` / ``d_r`` / ``d_v`` a head's no-rope, rotary and value
widths:

- ``c_q = RMSNorm(u W_qa)`` [r_q]; ``q = c_q W_qb`` -> [H, d_n + d_r] =
  ``q_nope | q_pe`` (scope ``mla.q``);
- ``u W_kva`` [r_kv + d_r] = ``c | k_pe``; ``c_kv = RMSNorm(c)``; ``k_pe``
  is ONE vector shared by the heads; RoPE on ``q_pe`` and ``k_pe`` over
  their ``d_r`` dimensions, rotate-half, with YaRN's frequencies
  (``models/layers.py yarn_inv_freq``) (scope ``mla.kv``);
- ``c_kv W_kvb`` -> [H, d_n + d_v] = ``k_nope | v``; scores ``(q_nope .
  k_nope + q_pe . k_pe) * s`` with ``s = (d_n + d_r) ** -0.5 *
  yarn_mscale(factor, mscale_all_dim) ** 2``; causal softmax in float32;
  ``sum p v`` [H, d_v] -> ``W_o``.

What a position leaves in the cache is ``c_kv`` (normed) and ``k_pe``
(roped): ``r_kv + d_r`` numbers a layer, whatever ``H`` (``llm/
kv_cache.py``: the latent pool).  ``models/attention.py
latent_attention`` computes the above two ways, ABSORBED for a decode
step and EXPANDED for a prefill and for training; its docstring has
both.

The sparse FFN: ``shared(x) + routed(x)``; shared is a SwiGLU of width
``moe_d_ff * n_shared_experts`` (scope ``moe.shared``); the router takes
``sigmoid(x W_g)`` over ``n_experts`` in float32, chooses the
``experts_per_token`` largest of ``score + expert_bias``
(``e_score_correction_bias``; the source's ``n_group`` = ``topk_group`` =
1 make its grouped choice the identity, so there is no grouping here),
weighs them by their scores over the chosen ones' sum, times
``routed_scaling_factor``.  ``expert_bias`` takes no gradient.  A layer
may hold a share of its experts (``first_expert``, ``held_experts``:
``ops/moe.py``); the shared expert is whole on every chip.

``MLAttention`` has two options that ``models/kimi_linear.py``'s latent
layers take: ``q_lora_rank=None`` (ONE matrix ``wq`` makes the queries: no
``wq_a``, norm or ``wq_b``) and ``mla_use_nope`` (NO rotation of ``q_pe`` and
``k_pe``, and ``s = (d_n + d_r) ** -0.5`` alone).  The layer loop, the
block and the FFN are ``models/decoder.py``'s; this file is the config,
the latent attention (``MLA_KIND``: what it is called in the tree and what
it keeps) and the init.

With a cache the contract is the other families' with ONE pool:
``latent_pages`` [layers, pages, page, row], carried whole through the
layers; a position < 0 is padding.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

from .attention import latent_attention
from .decoder import Decoder, Mixer, decoder_rules, next_token_loss
from .layers import RMSNorm, _rope, init_by_leaf, yarn_mscale

MLA = "mla"                 # every layer's kind
ROUTE_NORM_EPS = 1e-20      # in the sum of the chosen experts' scores
EXPERT_BIAS_STD = 0.005     # how init draws ``expert_bias``


def routed_experts(cfg) -> dict:
    """``ops/moe.py MoEMLP``'s arguments for a config with Kimi-K2's FFN
    names (``models/kimi_linear.py``'s too)."""
    return dict(d_ff=cfg.moe_d_ff, num_experts=cfg.n_experts,
                top_k=cfg.experts_per_token, scoring="sigmoid",
                select_bias=True, norm_eps=ROUTE_NORM_EPS,
                routed_scaling_factor=cfg.routed_scaling_factor,
                first_expert=cfg.first_expert,
                held_experts=cfg.held_experts)


@dataclass(frozen=True)
class KimiK2Config:
    """moonshotai/Kimi-K2.5's language model as published (the
    defaults): 61 layers of 7168, 64 heads over ranks 1536 | 512 and
    head widths 128 | 64 | 128, layer 0 a dense SwiGLU of 18432, layers
    1-60 a shared expert and top-8 of 384 experts of width 2048."""
    vocab_size: int = 163840
    n_layer: int = 61
    d_model: int = 7168
    n_head: int = 64
    # None: ONE matrix ``wq`` makes the queries, no rank and no norm
    # between (Kimi-Linear's MLA layers, models/kimi_linear.py).
    q_lora_rank: Optional[int] = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    d_ff: int = 18432                   # the dense layers' width
    n_dense_layers: int = 1
    moe_d_ff: int = 2048                # one expert's width
    n_experts: int = 384                # what the router scores
    experts_per_token: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.827
    # The share of the experts held here (ops/moe.py); None: all.
    first_expert: int = 0
    held_experts: Optional[int] = None
    rope_theta: float = 50000.0
    # YaRN (``rope_scaling``)
    rope_factor: float = 64.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    # True: NO position encoding; the 64-wide shared part is attended as
    # it is projected and the scale is ``(d_n + d_r) ** -0.5`` alone.
    mla_use_nope: bool = False
    max_seq: int = 262144
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    attn_impl: str = "dense"
    remat: bool = True
    mesh: Any = None

    def __post_init__(self):
        if yarn_mscale(self.rope_factor, self.rope_mscale) \
                != yarn_mscale(self.rope_factor, self.rope_mscale_all_dim):
            raise ValueError(
                "mscale != mscale_all_dim would scale RoPE's cos and sin; "
                "the published config has both 1 and this block writes "
                "that case down")

    @staticmethod
    def tiny(**overrides) -> "KimiK2Config":
        """The shape at a test's size: one dense layer and two sparse
        ones; 64 wide, 4 heads over ranks 32 | 24 and widths 16 | 8 |
        16 (a latent row of 32, padded to 128), a dense FFN of 96, a
        shared expert and top-2 of 8 experts of width 32; YaRN over 32
        original positions so that 128 positions reach the ramp."""
        return KimiK2Config(**{**dict(
            vocab_size=256, n_layer=3, d_model=64, n_head=4,
            q_lora_rank=32, kv_lora_rank=24, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, d_ff=96, moe_d_ff=32,
            n_experts=8, experts_per_token=2, rope_theta=10000.0,
            rope_factor=4.0, rope_original_max=32, max_seq=128,
            dtype=jnp.float32, param_dtype=jnp.float32), **overrides})

    @property
    def n_moe_layers(self) -> int:
        return max(self.n_layer - self.n_dense_layers, 0)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def yarn(self):
        """``models/layers.py _rope``'s ``yarn`` argument."""
        return (float(self.rope_factor), int(self.rope_original_max),
                float(self.rope_beta_fast), float(self.rope_beta_slow))

    @property
    def softmax_scale(self) -> float:
        """``(d_n + d_r) ** -0.5 * mscale ** 2``: YaRN's temperature is
        in the scale, both sides of the product (no rotation: none)."""
        if self.mla_use_nope:
            return self.qk_head_dim ** -0.5
        return self.qk_head_dim ** -0.5 * yarn_mscale(
            self.rope_factor, self.rope_mscale_all_dim) ** 2

    def attention_params(self) -> int:
        """One layer's five attention matrices (four where
        ``q_lora_rank`` is None), in parameters."""
        return mla_params(self)

    # What ``models/decoder.py`` reads besides the fields: the layers'
    # one kind, and the sparse layers' FFN.
    @property
    def layer_types(self):
        return (MLA,) * self.n_layer

    @property
    def mixers(self):
        return MIXERS

    experts = property(routed_experts)
    shared_d_ff = property(lambda self: self.moe_d_ff
                           * self.n_shared_experts)


def mla_params(cfg) -> int:
    """One latent-attention layer's matrices, in parameters (``cfg``:
    whatever ``MLAttention`` takes)."""
    h = cfg.n_head
    wq = cfg.d_model * h * cfg.qk_head_dim if cfg.q_lora_rank is None \
        else cfg.d_model * cfg.q_lora_rank \
        + cfg.q_lora_rank * h * cfg.qk_head_dim
    return wq + cfg.d_model * (cfg.kv_lora_rank + cfg.qk_rope_head_dim) \
        + cfg.kv_lora_rank * h * (cfg.qk_nope_head_dim + cfg.v_head_dim) \
        + h * cfg.v_head_dim * cfg.d_model


class MLAttention(nn.Module):
    """``cfg``: a KimiK2Config, or any config with its attention's names
    (``models/kimi_linear.py``'s: ``q_lora_rank`` None, ``mla_use_nope``)."""
    cfg: Any

    @nn.compact
    def __call__(self, u, cache=None):
        cfg = self.cfg
        h, r_kv = cfg.n_head, cfg.kv_lora_rank
        d_n, d_r, d_v = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                         cfg.v_head_dim)
        b, t = u.shape[0], u.shape[1]
        init = nn.initializers.normal(0.02)
        positions = cache["positions"] if cache is not None else None
        dense = functools.partial(nn.Dense, use_bias=False,
                                  dtype=cfg.dtype, kernel_init=init)
        with jax.named_scope("mla.q"):
            if cfg.q_lora_rank is None:
                q = dense(h * (d_n + d_r), name="wq")(u)
            else:
                c_q = RMSNorm(cfg.rms_eps, cfg.dtype, name="q_norm")(
                    dense(cfg.q_lora_rank, name="wq_a")(u))
                q = dense(h * (d_n + d_r), name="wq_b")(c_q)
            q = q.reshape(b, t, h, d_n + d_r)
            q_nope, q_pe = q[..., :d_n], q[..., d_n:]
        with jax.named_scope("mla.kv"):
            ckv = dense(r_kv + d_r, name="wkv_a")(u)
            c_kv = RMSNorm(cfg.rms_eps, cfg.dtype, name="kv_norm")(
                ckv[..., :r_kv])
            k_pe = ckv[..., r_kv:]
            if not cfg.mla_use_nope:
                k_pe = _rope(k_pe[:, :, None], cfg.rope_theta, positions,
                             cfg.yarn)[:, :, 0]
                q_pe = _rope(q_pe, cfg.rope_theta, positions, cfg.yarn)
        w_kvb = self.param("wkv_b", init, (r_kv, h * (d_n + d_v)),
                           jnp.float32).astype(cfg.dtype)
        att, pages = latent_attention(
            cfg, q_nope, q_pe, c_kv, k_pe,
            w_kvb.reshape(r_kv, h, d_n + d_v), cfg.softmax_scale, cache)
        with jax.named_scope("attn.out"):
            out = dense(cfg.d_model, name="wo")(att.reshape(b, t, h * d_v))
        return out, pages


class KimiK2(Decoder):
    """``models/decoder.py Decoder`` over a KimiK2Config: a step runs
    against the latent pool (``kv_cache`` = {"latent_pages" [layers,
    pages, page, row], "page_table"}, ``positions`` [B, T])."""


# What a latent layer keeps: ONE row a position, ``c_kv | k_pe``.
MLA_KIND = Mixer(MLAttention, "attn", ("latent_pages",), lambda cfg: {
    "latent_dim": cfg.kv_lora_rank, "rope_dim": cfg.qk_rope_head_dim},
    norm="attn_norm")
MIXERS = {MLA: MLA_KIND}


# ------------------------------------------------------ init, loss, rules

def _special_leaf(cfg: KimiK2Config, name: str, key, shape):
    if name.rsplit("/", 1)[-1] == "expert_bias":
        return EXPERT_BIAS_STD * jax.random.normal(key, shape, jnp.float32)
    return None


def kimi_k2_init(cfg: KimiK2Config, rng):
    """The weights from the seed, leaf by leaf (``models/layers.py
    init_by_leaf``): matrices, the embedding and the head normal(0,
    0.02), norm scales 1, and ``expert_bias`` normal(0,
    ``EXPERT_BIAS_STD``) in float32: NOT zero, where its absence could
    not show, and small enough not to collapse the router onto it.  At
    384 experts the sigmoid scores around a row's 8th and 9th largest lie
    ~0.004 apart, so a bias of 0.005 changes the choice of experts at
    most rows and still leaves it to the scores (LFM2's argument at its
    own spacing: ``models/lfm2.py lfm2_init``)."""
    return init_by_leaf(KimiK2, cfg, rng,
                        functools.partial(_special_leaf, cfg))


# (the source balances its experts through ``expert_bias``; its
# sequence-wise auxiliary loss has no weight in the published config)
kimi_k2_loss_fn = functools.partial(next_token_loss, KimiK2)


def kimi_k2_partition_rules():
    """``models/decoder.py decoder_rules`` after the latent attention's
    own: the low-rank projections column-parallel into their heads."""
    return decoder_rules(
        (r"(wq_a|wkv_a)/kernel$", PS("fsdp", None)),
        (r"wq_b/kernel$", PS("fsdp", "tensor")),
        (r"wkv_b$", PS("fsdp", "tensor")))
