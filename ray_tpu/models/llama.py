"""Llama family — RMSNorm + RoPE + GQA + SwiGLU decoder — and OLMoE,
which is this block plus two things: QK-norm (an RMSNorm over the whole
``q`` and ``k`` before the head split and RoPE) and sparse experts in
place of the dense SwiGLU (``ops/moe.py``: dropless top-k).

Covers the reference's Llama fine-tune workloads (ref: release/train_tests
LLM configs) natively.  Same logical-axis discipline as gpt2.py, and the
same attention core (models/attention.py): grouped KV heads are stored
grouped in the paged cache and repeated to the query heads for the
full forward.  The layer loop, the block, that attention's wrapper and
the FFN are ``models/decoder.py``'s; this file is the config and the row.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Optional

import jax.numpy as jnp

from .decoder import (Decoder, _next_token_xent, attention_kind,
                      decoder_rules, gqa, next_token_loss)
from .layers import _rope, init_by_leaf  # noqa: F401 (_rope: a fault tool's)

ATTENTION = "attention"     # every layer's kind


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    n_layer: int = 8
    n_head: int = 8
    n_kv_head: int = 4
    d_model: int = 512
    d_ff: int = 1408
    max_seq: int = 2048
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # What the weights are held in: init makes each leaf in float32 and
    # casts it to this (OLMoE: bfloat16, as its checkpoint).
    param_dtype: Any = jnp.float32
    attn_impl: str = "dense"
    remat: bool = True
    mesh: Any = None
    # OLMoE (Muennighoff et al. 2024): RMSNorm over the full-width q and
    # k; n_experts > 0 makes every block's FFN ``experts_per_token`` of
    # ``n_experts`` gated experts of width ``d_ff`` each.
    qk_norm: bool = False
    n_experts: int = 0
    experts_per_token: int = 0
    norm_topk_prob: bool = False
    moe_aux_weight: float = 0.01      # load-balancing loss
    moe_z_weight: float = 0.001       # router z-loss

    @staticmethod
    def tiny() -> "LlamaConfig":
        return LlamaConfig(vocab_size=512, n_layer=2, n_head=4, n_kv_head=2,
                           d_model=128, d_ff=384, max_seq=128)

    @staticmethod
    def olmoe_tiny(**overrides) -> "LlamaConfig":
        """OLMoE's shape at a test's size: 2 layers, 64 wide, 4 heads of
        16, top-2 of 8 experts of width 32."""
        return LlamaConfig(**{**dict(
            vocab_size=256, n_layer=2, n_head=4, n_kv_head=4, d_model=64,
            d_ff=32, max_seq=128, qk_norm=True, n_experts=8,
            experts_per_token=2, dtype=jnp.float32), **overrides})

    @staticmethod
    def olmoe_1b_7b(**overrides) -> "LlamaConfig":
        """allenai/OLMoE-1B-7B-0125-Instruct as published: 16 layers,
        2048 wide, 16 heads of 128, top-8 of 64 experts of width 1024,
        bf16 weights."""
        return LlamaConfig(**{**dict(
            vocab_size=50304, n_layer=16, n_head=16, n_kv_head=16,
            d_model=2048, d_ff=1024, max_seq=4096, qk_norm=True,
            n_experts=64, experts_per_token=8,
            param_dtype=jnp.bfloat16), **overrides})

    @staticmethod
    def llama2_7b() -> "LlamaConfig":
        return LlamaConfig(vocab_size=32000, n_layer=32, n_head=32,
                           n_kv_head=32, d_model=4096, d_ff=11008,
                           max_seq=4096)

    # What ``models/decoder.py`` reads: the layers' kinds, and the FFN.
    @property
    def layer_types(self):
        return (ATTENTION,) * self.n_layer

    @property
    def mixers(self):
        return MIXERS

    @property
    def n_dense_layers(self) -> int:
        return 0 if self.n_experts else self.n_layer

    @property
    def experts(self):
        """``ops/moe.py MoEMLP``'s arguments (None: every FFN dense)."""
        if not self.n_experts:
            return None
        return dict(d_ff=self.d_ff, num_experts=self.n_experts,
                    top_k=self.experts_per_token,
                    norm_topk_prob=self.norm_topk_prob)

    def _ffn_params_per_token(self) -> int:
        """Matmul weights one token passes through in a block's FFN: the
        dense SwiGLU, or its k experts and the router."""
        if self.n_experts:
            return (3 * self.d_model * self.d_ff * self.experts_per_token
                    + self.d_model * self.n_experts)
        return 3 * self.d_model * self.d_ff

    def flops_per_token(self) -> float:
        head_dim = self.d_model // self.n_head
        n_params = (self.vocab_size * self.d_model * 2
                    + self.n_layer * (
                        self.d_model * self.d_model            # q
                        + 2 * self.d_model * self.n_kv_head * head_dim
                        + self.d_model * self.d_model          # o
                        + self._ffn_params_per_token()))
        attn = 6 * 2 * self.n_layer * self.d_model * self.max_seq
        return 6.0 * n_params + attn

    def decode_flops_per_token(self,
                               context_len: Optional[int] = None) -> float:
        """FLOPs to DECODE one token with a KV cache at ``context_len``
        (defaults to max_seq/2): forward-only 2-FLOPs-per-matmul-weight
        plus one read of the cached K/V per layer (QK^T + PV over all
        n_head query heads — GQA shrinks the cache, not the attention
        arithmetic).  The training ``flops_per_token`` 6ND count would
        overstate decode MFU 3x."""
        head_dim = self.d_model // self.n_head
        ctx = self.max_seq // 2 if context_len is None else context_len
        matmul_params = (self.vocab_size * self.d_model   # lm_head only
                         + self.n_layer * (
                             self.d_model * self.d_model
                             + 2 * self.d_model * self.n_kv_head * head_dim
                             + self.d_model * self.d_model
                             + self._ffn_params_per_token()))
        attn = 4 * self.n_layer * self.d_model * ctx
        return 2.0 * matmul_params + attn


class Llama(Decoder):
    """``models/decoder.py Decoder`` over a LlamaConfig: every layer
    grouped-query attention with RoPE (OLMoE: a QK-norm over the width),
    its leaves the layer's own (``layer_i/wq``), and a dense SwiGLU or
    OLMoE's experts; the head untied.  ``k_pages`` / ``v_pages`` are [L,
    pages, page, h_kv*d] (the GROUPED heads, folded)."""


MIXERS = {ATTENTION: attention_kind(
    lambda cfg, name: functools.partial(
        gqa, cfg, qk_norm="width" if cfg.qk_norm else None),
    None, norm="attn_norm", residual_scope="attn.out")}


def llama_init(cfg: LlamaConfig, rng):
    """The weights from the seed, leaf by leaf (``models/layers.py``
    ``init_by_leaf``): every matrix normal(0, 0.02), every norm's scale
    1, cast to ``cfg.param_dtype``."""
    return init_by_leaf(Llama, cfg, rng)


llama_loss_fn = functools.partial(next_token_loss, Llama)


def olmoe_loss_fn(cfg: LlamaConfig, params, batch,
                  with_metrics: bool = False):
    """Mean next-token cross entropy + ``moe_aux_weight`` x the
    load-balancing loss + ``moe_z_weight`` x the router z-loss, both
    summed over the layers (ops/moe.py ``moe_losses``).  With
    ``with_metrics`` returns (loss, {"ce", "moe_load_balancing",
    "moe_router_z", "moe_max_load_over_mean"}) for a step built with
    ``has_aux``."""
    from ..ops.moe import moe_losses

    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    logits, state = Llama(cfg).apply(params, inputs,
                                     mutable=["intermediates"])
    ce = _next_token_xent(logits, targets)
    moe = moe_losses(state["intermediates"])
    loss = ce + cfg.moe_aux_weight * moe["load_balancing"] \
        + cfg.moe_z_weight * moe["router_z"]
    if not with_metrics:
        return loss
    return loss, {"ce": ce, **{f"moe_{k}": v for k, v in moe.items()}}


# A Llama tree has no leaf of its own, and OLMoE's experts are among the
# rules every decoder's tree shares.
llama_partition_rules = olmoe_partition_rules = decoder_rules
