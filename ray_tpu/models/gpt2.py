"""GPT-2 — the pretraining flagship (BASELINE.json: tokens/sec/chip).

TPU-first design notes:
- bfloat16 activations/params with fp32 master-less optics (optax handles
  fp32 moments), matmuls hit the MXU with preferred_element_type fp32;
- every weight/activation dim carries a logical name consumed by
  ray_tpu.parallel.sharding rules (DP/FSDP/TP = table change);
- attention impl selectable (models/attention.py, the core this block
  shares with llama.py): "dense", "flash", "ring" or "ulysses";
- ``c_attn`` is stored as ONE [d, 3*H*D] matrix, columns [q | k | v];
  where the flash kernels run per shard of a mesh it is applied through
  a view by shard of the heads (``FusedQKV``), so the split into heads
  moves that weight across the ``tensor`` axis and no activation;
- jax.checkpoint per block when ``remat``: a block keeps its input AND
  the residual stream after its attention sublayer (``ATTN_RESIDUAL``,
  already summed over the ``tensor`` axis), so the backward pass reads
  that sum where it would make ``c_proj``'s matmul and all-reduce again,
  and recomputes the rest;
- ``jax.named_scope`` names the parts (``embed``, ``attn.qkv``,
  ``attn.core``, ``attn.out``, ``mlp``, ``lm_head``, ``loss``) inside
  flax's own module scopes (``h_<i>``, ``ln_f``): a device trace and an
  HLO dump say whose each operation is.  Metadata only.

Role-equivalent to the reference's GPT-2 release-test workloads (ref:
release/train_tests LLM configs; the reference trains them via
torch+DeepSpeed, here the model is native).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..parallel.sharding import logical_shards
from ..parallel.sharding import with_logical_constraint as _constrain
from ..util import xprof
from .attention import attention, attention_qkv, qkv_by_head
from .layers import (XentLayout, chunked_xent, chunked_xent_over,
                     served_position, xent_layout)


@dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    n_layer: int = 12
    n_head: int = 12
    d_model: int = 768
    d_ff: int = 3072
    max_seq: int = 1024
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16
    attn_impl: str = "dense"          # dense | flash | ring | ulysses
    remat: bool = True
    mesh: Any = None                  # jax Mesh the activations lie on
    # Mixture-of-Experts: >0 turns every ``moe_every``-th block's MLP
    # into a dropless MoEMLP (ops/moe.py) of two-matrix GELU experts.
    moe_num_experts: int = 0
    moe_every: int = 2
    moe_top_k: int = 2
    moe_aux_weight: float = 0.01

    @staticmethod
    def small() -> "GPT2Config":
        return GPT2Config()

    @staticmethod
    def tiny() -> "GPT2Config":
        return GPT2Config(vocab_size=512, n_layer=2, n_head=4, d_model=128,
                          d_ff=512, max_seq=128)

    @staticmethod
    def medium() -> "GPT2Config":
        return GPT2Config(n_layer=24, n_head=16, d_model=1024, d_ff=4096)

    def flops_per_token(self) -> float:
        """Approximate training FLOPs per token (fwd+bwd ≈ 6N + attn)."""
        n_params = (self.vocab_size * self.d_model
                    + self.max_seq * self.d_model
                    + self.n_layer * (4 * self.d_model ** 2
                                      + 2 * self.d_model * self.d_ff))
        attn = 6 * 2 * self.n_layer * self.d_model * self.max_seq
        return 6.0 * n_params + attn

    def decode_flops_per_token(self,
                               context_len: Optional[int] = None) -> float:
        """FLOPs to DECODE one token with a KV cache at ``context_len``
        (defaults to max_seq/2, the mean context of a full generation):
        2 FLOPs per matmul weight — forward only, the training 6ND
        count would overstate decode MFU 3x — plus reading the cached
        K/V once per layer (QK^T + PV).  Embedding/positional lookups
        are gathers, not matmuls, so only the tied unembedding
        projection counts for wte."""
        ctx = self.max_seq // 2 if context_len is None else context_len
        matmul_params = (self.vocab_size * self.d_model
                         + self.n_layer * (4 * self.d_model ** 2
                                           + 2 * self.d_model * self.d_ff))
        attn = 4 * self.n_layer * self.d_model * ctx
        return 2.0 * matmul_params + attn


class FusedQKV(nn.Module):
    """``c_attn``: the ONE projection that makes q, k and v.  Its
    parameters are ``nn.Dense``'s, name for name and shape for shape
    (``kernel`` [d, 3*H*D] with the columns [q | k | v], ``bias``
    [3*H*D]), so the checkpoints, the partition rules and the optimizer
    see one tree whichever way it is applied:

    - plainly, as ``nn.Dense`` does it: [B, T, d] -> [B, T, 3*H*D];
    - ``by_head`` (training's flash path across a mesh,
      ``attention.qkv_by_head``): through the kernel viewed by shard of
      the heads, [shards, d, 3 * H/shards * D], a shard's own heads' q,
      k and v columns side by side and the shards, as many as the
      table's ``heads`` row cuts H into on this mesh, in front and
      constrained to that row -> [shards, B, T, 3 * H/shards * D]: each
      ``tensor`` shard's block is the plain ``qkv`` of its own heads,
      made by the plain matmul.  The STORED columns lie on the
      ``tensor`` axis as two halves of [q | k | v]; the view's
      constraint moves the WEIGHT to the heads (9.8 MB a layer at
      gpt2-large, bf16, off the activations' path, and its gradient
      back the same way) and nothing moves after the matmul.  (A view
      [d, 3, H, D] with an output [B, T, 3, H, D] says the same; the
      TPU's compiler lays that output out sequence-minor and copies it,
      PERF.md section 6, PR 40.)"""
    cfg: GPT2Config

    @nn.compact
    def __call__(self, x, by_head: bool = False):
        cfg = self.cfg
        d, h = cfg.d_model, cfg.n_head
        kernel = self.param("kernel", nn.initializers.normal(0.02),
                            (d, 3 * d), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros_init(), (3 * d,),
                          jnp.float32)
        x, kernel, bias = nn.dtypes.promote_dtype(x, kernel, bias,
                                                  dtype=cfg.dtype)
        if not by_head:
            y = jax.lax.dot_general(
                x, kernel, (((x.ndim - 1,), (0,)), ((), ())))
            return y + jnp.reshape(bias, (1,) * (y.ndim - 1) + (-1,))
        # [d, 3, H, D] -> [shards, d, 3 * H/shards * D]: a shard's own
        # heads' q, k and v columns side by side, the shards in front.
        n = logical_shards(cfg.mesh, "heads", h)
        kernel = kernel.reshape(d, 3, n, -1).transpose(2, 0, 1, 3)
        kernel = _constrain(kernel.reshape(n, d, -1),
                            ("heads", None, None), cfg.mesh)
        bias = bias.reshape(3, n, -1).transpose(1, 0, 2).reshape(n, -1)
        y = jnp.einsum("btd,ndc->nbtc", x, kernel) + bias[:, None, None]
        return _constrain(y, ("heads", "batch", None, None), cfg.mesh)


# The one value a remat'd block keeps beside its input: the residual
# stream after the attention sublayer.
ATTN_RESIDUAL = "attn_residual"


class Block(nn.Module):
    cfg: GPT2Config
    use_moe: bool = False

    @nn.compact
    def __call__(self, x, cache=None):
        cfg = self.cfg
        h = cfg.n_head
        d_head = cfg.d_model // h
        y = nn.LayerNorm(dtype=cfg.dtype, name="ln_1")(x)
        with jax.named_scope("attn.qkv"):
            qkv = FusedQKV(cfg, name="c_attn")(
                y, by_head=cache is None and qkv_by_head(cfg))
        b, t = x.shape[0], x.shape[1]
        if cache is None:
            # Training: q, k, v stay where c_attn left them and the
            # output comes as c_proj reads it (attention_qkv).
            att, new_cache = attention_qkv(cfg, qkv, h), None
        else:
            # This step's K/V go into the paged pool (prefill and
            # single-token decode alike) and q attends against the
            # gathered history.
            with jax.named_scope("attn.qkv"):
                q, k, v = (part.reshape(b, t, h, d_head)
                           for part in jnp.split(qkv, 3, axis=-1))
            att, new_cache = attention(cfg, q, k, v, cache)
        with jax.named_scope("attn.out"):
            att = att.reshape(b, t, cfg.d_model)
            att = nn.Dense(cfg.d_model, dtype=cfg.dtype, name="c_proj",
                           kernel_init=nn.initializers.normal(
                               0.02 / (2 * cfg.n_layer) ** 0.5))(att)
            x = x + att
            if cache is None:
                # Kept across the remat boundary: the SUM over the
                # ``tensor`` axis, laid out as the block's input is.
                x = checkpoint_name(x, ATTN_RESIDUAL)
        y = nn.LayerNorm(dtype=cfg.dtype, name="ln_2")(x)
        with jax.named_scope("mlp"):
            if self.use_moe:
                from ..ops.moe import MoEMLP

                y = MoEMLP(d_model=cfg.d_model, d_ff=cfg.d_ff,
                           num_experts=cfg.moe_num_experts,
                           top_k=cfg.moe_top_k,
                           dtype=cfg.dtype, name="moe_mlp")(
                               y, None if cache is None
                               else cache["positions"] >= 0)
            else:
                y = nn.Dense(cfg.d_ff, dtype=cfg.dtype, name="mlp_in",
                             kernel_init=nn.initializers.normal(0.02))(y)
                y = _constrain(y, ("batch", "seq", "mlp"), cfg.mesh)
                y = nn.gelu(y)
                y = nn.Dense(cfg.d_model, dtype=cfg.dtype,
                             name="mlp_out",
                             kernel_init=nn.initializers.normal(
                                 0.02 / (2 * cfg.n_layer) ** 0.5))(y)
            out = x + y
        return out if new_cache is None else (out, new_cache)


class GPT2(nn.Module):
    cfg: GPT2Config

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False,
                 kv_cache=None, positions=None, last=None):
        """Full forward (kv_cache=None) or incremental decode step.

        Decode mode attends against the paged KV pool instead of
        recomputing the sequence: ``kv_cache`` is {"k_pages",
        "v_pages": [L, pages, page, h*d], "page_table": [B, P]} and
        ``positions`` [B, T] gives each new token's absolute position
        (negative = padding).  One prefill call (T = prompt length)
        populates the cache; each decode call appends T=1 tokens.
        Returns (logits, new_kv_cache): the pool it was given, carried
        whole through the layers and updated (llm/kv_cache.py says
        why) — token-identical to the full forward (pinned by
        tests/test_llm.py).  ``last`` (int32 [B], an index within T;
        None: every position) is the ONE position of each row the caller
        serves: the blocks still run, and write their K/V, over all T,
        and ``ln_f`` and the head run on that position alone: logits
        [B, 1, V]."""
        cfg = self.cfg
        decode = kv_cache is not None
        wte = self.param("wte", nn.initializers.normal(0.02),
                         (cfg.vocab_size, cfg.d_model), jnp.float32)
        wpe = self.param("wpe", nn.initializers.normal(0.01),
                         (cfg.max_seq, cfg.d_model), jnp.float32)
        t = tokens.shape[1]
        with jax.named_scope("embed"):
            if decode:
                pos = jnp.maximum(positions, 0)
                x = wte.astype(cfg.dtype)[tokens] \
                    + wpe.astype(cfg.dtype)[pos]
            else:
                x = wte.astype(cfg.dtype)[tokens] \
                    + wpe.astype(cfg.dtype)[:t]
            x = _constrain(x, ("batch", "seq", "embed"), cfg.mesh)
        block = Block
        if cfg.remat and not decode:
            # Decode steps are memory-light; remat would only slow them.
            block = nn.remat(
                Block, prevent_cse=False,
                policy=jax.checkpoint_policies.save_only_these_names(
                    ATTN_RESIDUAL))
        if decode:
            # ONE pool through every layer, updated where it lies.
            k_pages, v_pages = kv_cache["k_pages"], kv_cache["v_pages"]
        for i in range(cfg.n_layer):
            use_moe = (cfg.moe_num_experts > 0
                       and i % cfg.moe_every == cfg.moe_every - 1)
            blk = block(cfg, use_moe=use_moe, name=f"h_{i}")
            if decode:
                x, (k_pages, v_pages) = blk(
                    x, cache={"k_pages": k_pages, "v_pages": v_pages,
                              "layer": i,
                              "page_table": kv_cache["page_table"],
                              "positions": positions})
            else:
                x = blk(x)
            x = _constrain(x, ("batch", "seq", "embed"), cfg.mesh)
        if last is not None:
            x = served_position(x, last)
        x = nn.LayerNorm(dtype=cfg.dtype, name="ln_f")(x)
        if return_hidden:
            return x
        with jax.named_scope("lm_head"):
            logits = jnp.einsum("btd,vd->btv", x, wte.astype(cfg.dtype),
                                preferred_element_type=jnp.float32)
            logits = _constrain(logits, ("batch", "seq", "vocab"), cfg.mesh)
        if decode:
            return logits, {"k_pages": k_pages, "v_pages": v_pages,
                            "page_table": kv_cache["page_table"]}
        return logits


def gpt2_init(cfg: GPT2Config, rng) -> Any:
    import dataclasses

    # Init traces a tiny batch; sharding constraints (and the context-
    # parallel kernels) don't apply to it and would reject the shapes —
    # strip them.
    init_cfg = dataclasses.replace(cfg, mesh=None, attn_impl="dense")
    tokens = jnp.zeros((1, min(cfg.max_seq, 8)), jnp.int32)
    return GPT2(init_cfg).init(rng, tokens)


def loss_layout(cfg: GPT2Config, shape, loss_chunk: int) -> XentLayout:
    """How ``gpt2_loss_fn`` computes the loss of ``[B, T] = shape`` input
    tokens: ``layers.xent_layout`` on ``cfg.mesh``, but for an MoE config,
    whose auxiliary loss reads the whole forward.  Pure: the tests and the
    step's telemetry ask what the program asked."""
    if cfg.moe_num_experts > 0:
        return XentLayout()
    return xent_layout(cfg.mesh, shape, loss_chunk)


def gpt2_loss_fn(cfg: GPT2Config, params, batch,
                 loss_chunk: int = 128) -> jnp.ndarray:
    """Next-token cross entropy; batch: {tokens [B, T+1] int32}.
    MoE configs add the load-balancing auxiliary loss (ops/moe.py)."""
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    moe = cfg.moe_num_experts > 0
    layout = loss_layout(cfg, inputs.shape, loss_chunk)
    xprof.note("loss", path=layout.path, token_shards=layout.shards,
               chunk=layout.chunk)
    if layout.path == "chunked":
        # No [B, T, V] logits: one chip scans all the tokens, the chips
        # of a mesh each their own share of them (an odd vocabulary
        # leaves GSPMD the same whole logits on every ``tensor`` shard).
        x = GPT2(cfg).apply(params, inputs, return_hidden=True)
        wte = params["params"]["wte"].astype(cfg.dtype)
        if layout.shards == 1:
            return chunked_xent(x, wte, targets, layout.chunk)
        return chunked_xent_over(cfg.mesh, layout, x, wte, targets)
    if moe:
        logits, state = GPT2(cfg).apply(params, inputs,
                                        mutable=["intermediates"])
        from ..ops.moe import moe_losses

        aux = moe_losses(state["intermediates"])["load_balancing"]
    else:
        logits = GPT2(cfg).apply(params, inputs)
        aux = 0.0
    with jax.named_scope("loss"):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(logp, targets[..., None],
                                 axis=-1)[..., 0]
        return -jnp.mean(ll) + cfg.moe_aux_weight * aux


def gpt2_partition_rules():
    """Default fsdp+tensor partition rules for GPT-2 param trees, in
    ``match_partition_rules`` form ((regex, PartitionSpec) pairs, first
    match wins).  THE description of how the weights shard: placed by
    ``train.distributed.fitted_state_specs``, persisted by the elastic
    checkpoint plane.  An output dimension lies on the mesh axis the
    activation table (parallel/sharding.py) gives the activation it
    produces (vocab/heads/mlp → ``tensor``; pinned by
    tests/test_parallel.py), the other dimension on ``fsdp``."""
    from jax.sharding import PartitionSpec as PS

    return (
        ("wte$", PS("tensor", "fsdp")),
        ("wpe$", PS()),
        (r"c_attn/kernel$", PS("fsdp", "tensor")),
        (r"c_proj/kernel$", PS("tensor", "fsdp")),
        (r"mlp_in/kernel$", PS("fsdp", "tensor")),
        (r"mlp_out/kernel$", PS("tensor", "fsdp")),
        (r"moe_mlp/w_in$", PS("expert", "fsdp", "tensor")),
        (r"moe_mlp/w_out$", PS("expert", "tensor", "fsdp")),
        (r"moe_mlp/router$", PS("fsdp", None)),
        (r"(bias|scale)$", PS()),
    )
