"""Llama family — RMSNorm + RoPE + GQA + SwiGLU decoder — and OLMoE,
which is this block plus two things: QK-norm (an RMSNorm over the whole
``q`` and ``k`` before the head split and RoPE) and sparse experts in
place of the dense SwiGLU (``ops/moe.py``: dropless top-k).

Covers the reference's Llama fine-tune workloads (ref: release/train_tests
LLM configs) natively.  Same logical-axis discipline as gpt2.py, and the
same attention core (models/attention.py): grouped KV heads are stored
grouped in the paged cache and repeated to the query heads for the
full forward.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..parallel.sharding import with_logical_constraint as _constrain
from .attention import attention
from .layers import RMSNorm, _rope, init_by_leaf


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


class LlamaBlock(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, cache=None):
        cfg = self.cfg
        h, hk = cfg.n_head, cfg.n_kv_head
        d_head = cfg.d_model // h
        b, t = x.shape[0], x.shape[1]
        y = RMSNorm(cfg.rms_eps, cfg.dtype, name="attn_norm")(x)
        init = nn.initializers.normal(0.02)
        positions = cache["positions"] if cache is not None else None
        # Scope names as in models/gpt2.py (metadata only).
        with jax.named_scope("attn.qkv"):
            q = nn.Dense(h * d_head, use_bias=False, dtype=cfg.dtype,
                         kernel_init=init, name="wq")(y)
            k = nn.Dense(hk * d_head, use_bias=False, dtype=cfg.dtype,
                         kernel_init=init, name="wk")(y)
            v = nn.Dense(hk * d_head, use_bias=False, dtype=cfg.dtype,
                         kernel_init=init,
                         name="wv")(y).reshape(b, t, hk, d_head)
            if cfg.qk_norm:     # over the whole width, before the split
                with jax.named_scope("attn.qk_norm"):
                    q = RMSNorm(cfg.rms_eps, cfg.dtype, name="q_norm")(q)
                    k = RMSNorm(cfg.rms_eps, cfg.dtype, name="k_norm")(k)
            q = _rope(q.reshape(b, t, h, d_head), cfg.rope_theta,
                      positions)
            k = _rope(k.reshape(b, t, hk, d_head), cfg.rope_theta,
                      positions)
        # The cache stores the hk GROUPED heads (post-RoPE); the full
        # forward repeats them to h.
        att, new_cache = attention(cfg, q, k, v, cache)
        with jax.named_scope("attn.out"):
            att = att.reshape(b, t, cfg.d_model)
            att = nn.Dense(cfg.d_model, use_bias=False, dtype=cfg.dtype,
                           kernel_init=init, name="wo")(att)
            x = x + att
        y = RMSNorm(cfg.rms_eps, cfg.dtype, name="mlp_norm")(x)
        with jax.named_scope("mlp"):
            if cfg.n_experts:
                from ..ops.moe import MoEMLP

                down = MoEMLP(
                    d_model=cfg.d_model, d_ff=cfg.d_ff,
                    num_experts=cfg.n_experts,
                    top_k=cfg.experts_per_token, gated=True,
                    norm_topk_prob=cfg.norm_topk_prob, act=nn.silu,
                    dtype=cfg.dtype, name="moe")(
                        y, None if positions is None else positions >= 0)
            else:
                gate = nn.Dense(cfg.d_ff, use_bias=False, dtype=cfg.dtype,
                                kernel_init=init, name="w_gate")(y)
                up = nn.Dense(cfg.d_ff, use_bias=False, dtype=cfg.dtype,
                              kernel_init=init, name="w_up")(y)
                z = nn.silu(gate) * up
                z = _constrain(z, ("batch", "seq", "mlp"), cfg.mesh)
                down = nn.Dense(cfg.d_model, use_bias=False,
                                dtype=cfg.dtype, kernel_init=init,
                                name="w_down")(z)
            out = x + down
        return out if new_cache is None else (out, new_cache)


class Llama(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, tokens, kv_cache=None, positions=None):
        """Full forward (kv_cache=None) or incremental decode step
        against the paged KV pool — same contract as GPT2.__call__:
        ``k_pages`` / ``v_pages`` are [L, pages, page, h_kv*d] (the
        GROUPED heads, folded), carried whole through the layers;
        decode mode returns (logits, new_kv_cache)."""
        cfg = self.cfg
        decode = kv_cache is not None
        emb = self.param("embed", nn.initializers.normal(0.02),
                         (cfg.vocab_size, cfg.d_model), jnp.float32)
        with jax.named_scope("embed"):
            x = emb.astype(cfg.dtype)[tokens]
            x = _constrain(x, ("batch", "seq", "embed"), cfg.mesh)
        block = LlamaBlock
        if cfg.remat and not decode:
            block = nn.remat(LlamaBlock, prevent_cse=False)
        if decode:
            # ONE pool through every layer, updated where it lies.
            k_pages, v_pages = kv_cache["k_pages"], kv_cache["v_pages"]
        for i in range(cfg.n_layer):
            blk = block(cfg, name=f"layer_{i}")
            if decode:
                x, (k_pages, v_pages) = blk(
                    x, cache={"k_pages": k_pages, "v_pages": v_pages,
                              "layer": i,
                              "page_table": kv_cache["page_table"],
                              "positions": positions})
            else:
                x = blk(x)
            x = _constrain(x, ("batch", "seq", "embed"), cfg.mesh)
        x = RMSNorm(cfg.rms_eps, cfg.dtype, name="norm_f")(x)
        head = self.param("lm_head", nn.initializers.normal(0.02),
                          (cfg.d_model, cfg.vocab_size), jnp.float32)
        with jax.named_scope("lm_head"):
            logits = jnp.einsum("btd,dv->btv", x, head.astype(cfg.dtype),
                                preferred_element_type=jnp.float32)
            logits = _constrain(logits, ("batch", "seq", "vocab"), cfg.mesh)
        if decode:
            return logits, {"k_pages": k_pages, "v_pages": v_pages,
                            "page_table": kv_cache["page_table"]}
        return logits


def llama_init(cfg: LlamaConfig, rng):
    """The weights from the seed, leaf by leaf (``models/layers.py``
    ``init_by_leaf``): every matrix normal(0, 0.02), every norm's scale
    1, cast to ``cfg.param_dtype``."""
    return init_by_leaf(Llama, cfg, rng)


def _next_token_xent(logits, targets):
    with jax.named_scope("loss"):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(logp, targets[..., None],
                                 axis=-1)[..., 0]
        return -jnp.mean(ll)


def llama_loss_fn(cfg: LlamaConfig, params, batch):
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    return _next_token_xent(Llama(cfg).apply(params, inputs), targets)


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


def llama_partition_rules():
    """Default fsdp+tensor partition rules for Llama param trees
    (``match_partition_rules`` form; see ``gpt2_partition_rules``)."""
    from jax.sharding import PartitionSpec as PS

    return (
        ("embed$", PS("tensor", "fsdp")),
        ("lm_head$", PS("fsdp", "tensor")),
        (r"w[qkv]/kernel$", PS("fsdp", "tensor")),
        (r"wo/kernel$", PS("tensor", "fsdp")),
        (r"(w_gate|w_up)/kernel$", PS("fsdp", "tensor")),
        (r"w_down/kernel$", PS("tensor", "fsdp")),
        (r"(scale|bias)$", PS()),
    )


def olmoe_partition_rules():
    """Llama's rules and the experts': every expert on every chip, its
    matrices sharded over fsdp x tensor on their ``d`` and ``f``
    dimensions (experts over an ``expert`` mesh axis is ROADMAP
    Reach 5's)."""
    from jax.sharding import PartitionSpec as PS

    return (
        (r"moe/(w_gate|w_up)$", PS(None, "fsdp", "tensor")),
        (r"moe/w_down$", PS(None, "tensor", "fsdp")),
        (r"moe/router$", PS("fsdp", None)),
    ) + llama_partition_rules()
