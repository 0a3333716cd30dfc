"""Cohere2-MoE family (HF ``model_type`` cohere2_moe; CohereLabs'
``command-a-plus-05-2026``, Command A+ 218B-A25B) — a sparse decoder whose
layers differ in their attention's kind alone: three ``sliding_attention``
layers, which see the last ``sliding_window`` positions and turn q and k by
RoPE, then one ``full_attention`` layer, which sees every earlier position
and encodes NO position; the block is PARALLEL, under ONE LayerNorm.

Layer ``l``, all alike but for the attention's kind:

- ``y = LN(x) = (x - mean x) / sqrt(var x + eps) * g`` (float32, no bias;
  ``models/layers.py LayerNorm``: the ONE norm of the layer);
- ``q = y W_q`` as ``n_head`` heads of ``head_dim`` (the config's own: 128
  x 128 = 16,384 over a model 4,096 wide), ``k = y W_k``, ``v = y W_v`` as
  ``n_kv_head`` heads, no bias, no QK-norm;
- ``sliding_attention``: q and k turned by RoPE over the whole head, theta
  ``rope_theta``, ADJACENT pairs ``(2i, 2i + 1)`` (the source's
  ``rope_gptj``); key ``j`` meets query ``i`` iff ``0 <= i - j <
  sliding_window``.  ``full_attention``: no rotation; iff ``j <= i``.
  Scores ``q . k * head_dim ** -0.5``, float32 softmax, ``W_o``;
- the router reads the same ``y``: ``s = sigmoid(y W_r)`` over ``n_experts``
  in float32, the ``experts_per_token`` largest chosen, weights ``s_e / sum
  of the chosen``; ``routed = sum_e w_e E_e(y)``, ``E(y) = (silu(y W_g) * (y
  W_u)) W_d`` of width ``d_ff``;
- ``shared = 1 / n sum_s S_s(y)``, ``n_shared_experts`` SwiGLUs of ``d_ff``
  AVERAGED: one SwiGLU of ``n x d_ff`` times ``1 / n`` (``shared_d_ff``,
  ``shared_multiplier``);
- ``x' = x + attn + routed + shared`` (``parallel_block``).

After the last layer one more LayerNorm, then ``logits = h E^T *
logit_scale`` with ``E`` the embedding (tied).

With a cache the K/V pool is in TWO GROUPS (``models.CacheSpec``:
``kv_layers`` the full layers, ``window_layers`` the sliding ones):
``k_pages`` / ``v_pages`` [full layers, pages, page, n_kv_head * head_dim]
through ``page_table``, every position of a sequence; ``window_k_pages`` /
``window_v_pages`` [sliding layers, max_batch x ring pages, page, the same
width] through ``window_table``, a ring of ``sliding_window`` positions a
sequence (``models/attention.py _ring``), however long it grows.

A layer may hold a share of its routed experts (``first_expert``,
``held_experts``: one chip's part under expert parallelism, ``ops/moe.py``).
The vision tower of the source is no part of this family: text alone.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax.numpy as jnp

from .decoder import (Attention, Decoder, attention_kind, decoder_rules,
                      next_token_loss, window_kind)
from .layers import LayerNorm, init_by_leaf

SLIDING = "sliding_attention"
FULL = "full_attention"


@dataclass(frozen=True)
class Cohere2MoeConfig:
    """CohereLabs/command-a-plus-05-2026's language model as published (the
    defaults): 32 layers of 4096, three ``sliding_attention`` (window 4,096,
    RoPE at theta 50,000) then one ``full_attention`` (no position
    encoding), eight times; 128 query heads of 128 on 8 K/V heads; top-8 of
    128 experts of width 4,096 by sigmoid scores, renormalised, beside 4
    shared experts of 4,096 averaged; vocabulary 262,144, tied."""
    vocab_size: int = 262144
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL) * 8
    d_model: int = 4096
    n_head: int = 128
    n_kv_head: int = 8
    head_dim: int = 128                 # NOT d_model / n_head
    sliding_window: int = 4096
    d_ff: int = 4096                    # one routed, one shared expert
    n_experts: int = 128                # what the router scores
    experts_per_token: int = 8
    n_shared_experts: int = 4
    # The share of the routed experts held here (ops/moe.py); None: all.
    first_expert: int = 0
    held_experts: Optional[int] = None
    logit_scale: float = 1.0
    max_seq: int = 200000
    rope_theta: float = 50000.0
    rms_eps: float = 1e-5               # the LayerNorms' epsilon
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    attn_impl: str = "dense"
    remat: bool = True
    mesh: Any = None

    def __post_init__(self):
        bad = set(self.layer_types) - {SLIDING, FULL}
        if bad:
            raise ValueError(f"layer_types holds {sorted(bad)}")

    @staticmethod
    def tiny(**overrides) -> "Cohere2MoeConfig":
        """The shape at a test's size: one period, 64 wide, 4 heads of 32
        (twice the model's width in all, as the source's 128 of 128 are
        four times) on 2 K/V heads, a window of 8, top-2 of 8 experts of
        width 32 beside 2 shared ones."""
        return Cohere2MoeConfig(**{**dict(
            vocab_size=256, layer_types=(SLIDING, SLIDING, SLIDING, FULL),
            d_model=64, n_head=4, n_kv_head=2, head_dim=32,
            sliding_window=8, d_ff=32,
            n_experts=8, experts_per_token=2, n_shared_experts=2,
            max_seq=128, rope_theta=10000.0, dtype=jnp.float32,
            param_dtype=jnp.float32), **overrides})

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    # What ``models/decoder.py`` reads besides the fields, as data: ONE
    # norm a layer under both sublayers, the norm's module, the tied head,
    # the kinds, and the FFN (no dense layer leads: the source's
    # ``first_k_dense_replace`` is 0).
    parallel_block = True
    norm = LayerNorm
    tied_head = True
    n_dense_layers = 0

    @property
    def mixers(self):
        return MIXERS

    @property
    def experts(self):
        """``ops/moe.py MoEMLP``'s arguments."""
        return dict(d_ff=self.d_ff, num_experts=self.n_experts,
                    top_k=self.experts_per_token, scoring="sigmoid",
                    norm_topk_prob=True, first_expert=self.first_expert,
                    held_experts=self.held_experts)

    @property
    def shared_d_ff(self) -> int:
        return self.d_ff * self.n_shared_experts

    @property
    def shared_multiplier(self) -> float:
        """The shared experts are averaged, not summed."""
        return 1.0 / self.n_shared_experts


class Cohere2Moe(Decoder):
    """``models/decoder.py Decoder`` over a Cohere2MoeConfig: a step runs
    against BOTH groups of the K/V pool (``kv_cache`` = {"k_pages",
    "v_pages", "window_k_pages", "window_v_pages", "page_table",
    "window_table"}, ``positions`` [B, T]; the module docstring has the
    shapes)."""


MIXERS = {
    # RoPE over adjacent pairs, a ring of ``sliding_window`` positions
    SLIDING: window_kind(
        lambda cfg, name: Attention(
            cfg, interleaved=True, window=cfg.sliding_window,
            core_scope="attn.window", name=name), norm="norm"),
    # no position encoding, every position kept
    FULL: attention_kind(functools.partial(
        Attention, rope=False, core_scope="attn.full"), norm="norm"),
}


# ------------------------------------------------------ init, loss, rules

def cohere2_moe_init(cfg: Cohere2MoeConfig, rng):
    """The weights from the seed, leaf by leaf (``models/layers.py
    init_by_leaf``): every matrix and the embedding normal(0, 0.02), every
    norm's scale 1, cast to ``cfg.param_dtype``."""
    return init_by_leaf(Cohere2Moe, cfg, rng)


# (the trainer's path is the dense definition, which masks the band)
cohere2_moe_loss_fn = functools.partial(next_token_loss, Cohere2Moe)

# A Cohere tree has no leaf the decoders' shared rules do not name.
cohere2_moe_partition_rules = decoder_rules
