"""Granite 4.0-H family (HF ``model_type`` granitemoehybrid) — layers of
two kinds in one model: Mamba-2 state-space mixers (Dao & Gu 2024,
"Transformers are SSMs") and, every few layers, grouped-query attention
with NO position encoding; every layer followed by sparse experts
(``ops/moe.py``, of which a chip may hold a share) plus one shared gated
MLP.  Granite's four multipliers scale the embedding, each residual
branch, the attention scores and the logits; the output head is the
embedding, tied.

Layer ``i``: ``h = RMSNorm(x)``; ``m = Mamba2(h)`` or ``Attn(h)`` by
``layer_types[i]``; ``x = x + residual_multiplier x m``; ``h = RMSNorm(x)``;
``x = x + residual_multiplier x (MoE(h) + Shared(h))``.

The Mamba-2 mixer (one group of B and C shared by all heads): ``[z | xBC |
dt] = h W_in``; ``xBC = silu(causal depthwise conv_4(xBC) + b)``, split
into ``x`` (heads x head size), ``B``, ``C`` (state size each); ``dt =
softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head the state ``S_t =
exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`` and ``y_t = S_t C_t + D x_t``; ``y =
RMSNorm(y x silu(z))`` over the whole inner width; ``y W_out``.  ``dt``,
the decay and the state are float32.

A forward over many positions (training, the full forward, the engine's
prefill) computes it in chunks (``ssm.scan``: within a chunk of
``mamba_chunk`` positions as matmuls, the state carried from chunk to
chunk); a decode step is the recurrence once (``ssm.step``).  With a
cache (``llm/kv_cache.py``: the paged K/V pool for the attention layers,
the state pool ``conv`` / ``ssm`` for the mixers, one slot a sequence)
each row's state is read from and written to its slot, the pools carried
whole through the layers.  A position < 0 is padding: it neither decays
nor feeds the state and is kept out of the conv window, so a prefill
padded to its bucket leaves the state of its last real position; a row
whose slot index lies outside the pool (a padded decode row) changes
nothing: its conv window is dropped, and its state, read from the index
clipped into the pool, goes back as it was read.  A forward whose first
position is 0 starts from a zero state whatever its slot held: that is
how a slot is cleared for the sequence that takes it.

The layer loop, the block, the attention wrapper and the FFN are
``models/decoder.py``'s; this file is the config, the mixer with its scan
and step, its kind (``MIXERS``: the names in the tree, what a layer
keeps) and the init.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

from .decoder import (Attention, Decoder, Mixer, attention_kind,
                      decoder_rules, next_token_loss)
from .layers import RMSNorm, init_by_leaf, slot_conv

MAMBA, ATTENTION = "mamba", "attention"


@dataclass(frozen=True)
class GraniteConfig:
    """ibm-granite/granite-4.0-h-small as published (the defaults): 40
    layers, attention at 5, 15, 25, 35; 4096 wide; 32 query and 8 K/V
    heads of 128; 128 Mamba heads of 64 with state 128; top-10 of 72
    experts of width 768 and a shared expert of width 1536."""
    vocab_size: int = 100352
    layer_types: Tuple[str, ...] = tuple(
        ATTENTION if i % 10 == 5 else MAMBA for i in range(40))
    d_model: int = 4096
    n_head: int = 32
    n_kv_head: int = 8
    d_ff: int = 768                     # one routed expert's width
    shared_d_ff: int = 1536
    n_experts: int = 72                 # what the router scores
    experts_per_token: int = 10
    # The share of the routed experts held here (expert parallelism:
    # ops/moe.py); None holds all ``n_experts``.
    first_expert: int = 0
    held_experts: Optional[int] = None
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk: int = 256
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.0078125
    logits_scaling: float = 16.0
    max_seq: int = 131072
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    attn_impl: str = "dense"
    remat: bool = True
    mesh: Any = None

    def __post_init__(self):
        if self.mamba_n_groups != 1:
            raise ValueError("one group of B and C is what is written "
                             "here (mamba_n_groups = 1)")
        bad = set(self.layer_types) - {MAMBA, ATTENTION}
        if bad:
            raise ValueError(f"layer_types holds {sorted(bad)}")

    @staticmethod
    def tiny(**overrides) -> "GraniteConfig":
        """The shape at a test's size: [mamba, mamba, attention, mamba],
        64 wide, 4 Mamba heads of 16 with state 16 and chunks of 8,
        top-2 of 8 experts of width 32, a shared expert of width 32."""
        return GraniteConfig(**{**dict(
            vocab_size=256, layer_types=(MAMBA, MAMBA, ATTENTION, MAMBA),
            d_model=64, n_head=4, n_kv_head=2, d_ff=32, shared_d_ff=32,
            n_experts=8, experts_per_token=2, mamba_n_heads=4,
            mamba_d_head=16, mamba_d_state=16, mamba_chunk=8, max_seq=128,
            attention_multiplier=1 / 16,    # 1 / head size, as the source's
            dtype=jnp.float32, param_dtype=jnp.float32), **overrides})

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    def layers_of(self, kind: str) -> int:
        return sum(t == kind for t in self.layer_types)

    def mixer_params(self) -> int:
        """One state-space layer's mixer matrices (``in_proj`` and
        ``out_proj``), in parameters."""
        return self.d_model * (self.d_inner + self.conv_dim
                               + self.mamba_n_heads) \
            + self.d_inner * self.d_model

    # What ``models/decoder.py`` reads besides the fields: the kinds, the
    # FFN (experts and the shared one in every layer), the tied head.
    n_dense_layers = 0
    tied_head = True

    @property
    def mixers(self):
        return MIXERS

    @property
    def experts(self):
        """``ops/moe.py MoEMLP``'s arguments."""
        return dict(d_ff=self.d_ff, num_experts=self.n_experts,
                    top_k=self.experts_per_token,
                    first_expert=self.first_expert,
                    held_experts=self.held_experts)


# ------------------------------------------------------------ the mixer

def ssd_scan(x, dt, a, b_mat, c_mat, chunk: int, state=None):
    """The state-space recurrence over T positions in chunks.

    x [B, T, H, P], dt [B, T, H] (0 at a padded position: no decay, no
    input), a [H] (negative), b_mat / c_mat [B, T, N], all float32;
    ``state`` [B, H, P, N] (None: zeros).  Returns (y [B, T, H, P] without
    the ``D x`` term, the state after the last position).

    With ``l_t = sum_{s <= t} dt_s a`` inside a chunk: the chunk's own
    part is ``y_t = sum_{s <= t} exp(l_t - l_s) (C_t . B_s) dt_s x_s``,
    two matmuls; what the chunk inherits is ``exp(l_t) S_in C_t``; and it
    hands on ``S_out = exp(l_Q) S_in + sum_s exp(l_Q - l_s) dt_s x_s
    B_s^T``.  T is filled up to whole chunks (of at most T) with dt = 0."""
    bsz, t, h, p = x.shape
    n = b_mat.shape[-1]
    chunk = min(chunk, t)
    fill = -t % chunk
    if fill:
        x, dt, b_mat, c_mat = (
            jnp.pad(z, [(0, 0), (0, fill)] + [(0, 0)] * (z.ndim - 2))
            for z in (x, dt, b_mat, c_mat))
    nc = (t + fill) // chunk

    def chunks(z):      # [B, nc*Q, ...] -> [nc, B, Q, ...]
        return jnp.moveaxis(
            z.reshape((bsz, nc, chunk) + z.shape[2:]), 1, 0)

    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    def one(s_in, blk):
        xc, dtc, bc, cc = blk
        log = jnp.cumsum(dtc * a, axis=1)                     # [B,Q,H]
        by_head = jnp.swapaxes(log, 1, 2)                     # [B,H,Q]
        # exp(l_t - l_s), t >= s; a masked entry's exponent may be
        # positive: mask the exponent, not the product.
        seg = by_head[..., :, None] - by_head[..., None, :]   # [B,H,Q,Q]
        decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
        cb = jnp.einsum("btn,bsn->bts", cc, bc)               # [B,Q,Q]
        xdt = xc * dtc[..., None]                             # [B,Q,H,P]
        y = jnp.einsum("bhts,bshp->bthp", cb[:, None] * decay, xdt)
        y = y + jnp.exp(log)[..., None] * jnp.einsum(
            "bhpn,btn->bthp", s_in, cc)
        to_end = jnp.exp(log[:, -1:, :] - log)                # [B,Q,H]
        s_out = jnp.exp(log[:, -1, :])[:, :, None, None] * s_in \
            + jnp.einsum("bshp,bsn->bhpn", to_end[..., None] * xdt, bc)
        return s_out, y

    if state is None:
        state = jnp.zeros((bsz, h, p, n), jnp.float32)
    state, y = jax.lax.scan(one, state,
                            tuple(chunks(z) for z in (x, dt, b_mat, c_mat)))
    y = jnp.moveaxis(y, 0, 1).reshape(bsz, nc * chunk, h, p)
    return y[:, :t], state


def _step_rows(ssm_pool, layer, slots, fresh, dt, a, x, b_mat, c_mat):
    """The recurrence once for every row of a decode batch, each row's
    state read from and written to its slot of layer ``layer`` of the
    WHOLE pool ``[L, slots, H, P, N]``, row by row, where it lies: no
    gathered copy of the batch's states (gathered and scattered, XLA
    moved six times a row's 4 MB where this moves three: my compiles for
    a v5e, PR 31).  Two passes: the states updated in place, then ``y_t =
    S_t C_t`` read from them (in one loop the read of the old state and
    the write of the new made the CPU's compiler copy the pool).  dt
    [B, H] (0: a padded row, whose slot index is clipped into the pool
    and whose state is written back as it was read), x [B, H, P], b_mat
    / c_mat [B, N].  Returns (y [B, H, P] without the ``D x`` term, the
    pool)."""
    decay = jnp.exp(dt * a)                                    # [B,H]
    xdt = dt[..., None] * x                                    # [B,H,P]
    rows = x.shape[0]
    one = (1, 1) + ssm_pool.shape[2:]

    def at(i):
        return (layer, jnp.clip(slots[i], 0, ssm_pool.shape[1] - 1),
                0, 0, 0)

    def update(i, pool):
        s = jax.lax.dynamic_slice(pool, at(i), one)[0, 0]
        s = jnp.where(fresh[i], 0.0, s.astype(jnp.float32))
        s = decay[i][:, None, None] * s \
            + xdt[i][:, :, None] * b_mat[i][None, None, :]
        return jax.lax.dynamic_update_slice(
            pool, s[None, None].astype(pool.dtype), at(i))

    ssm_pool = jax.lax.fori_loop(0, rows, update, ssm_pool)

    def read(i, ys):
        # a multiply and a sum in float32: no matmul rounds the state
        s = jax.lax.dynamic_slice(ssm_pool, at(i), one)[0, 0]
        y = jnp.sum(s.astype(jnp.float32) * c_mat[i][None, None, :],
                    axis=-1)
        return jax.lax.dynamic_update_slice(ys, y[None], (i, 0, 0))

    ys = jax.lax.fori_loop(0, rows, read, jnp.zeros(x.shape, jnp.float32))
    return ys, ssm_pool


def _load_rows(ssm_pool, layer, slots):
    """Each row's state [B, H, P, N] read from its slot of layer
    ``layer``, row by row (a slot index outside the pool is clipped:
    a padded row reads some other row's state, and nothing is made of
    it)."""
    last = ssm_pool.shape[1] - 1
    one = (1, 1) + ssm_pool.shape[2:]

    def row(i, out):
        s = jax.lax.dynamic_slice(
            ssm_pool, (layer, jnp.clip(slots[i], 0, last), 0, 0, 0), one)
        return jax.lax.dynamic_update_slice(out, s[0], (i, 0, 0, 0))

    return jax.lax.fori_loop(
        0, slots.shape[0], row,
        jnp.zeros(slots.shape + ssm_pool.shape[2:], ssm_pool.dtype))


def _store_rows(ssm_pool, layer, slots, states):
    """Write ``states`` [B, H, P, N] into their slots of layer ``layer``
    of the pool [L, slots, H, P, N], row by row, in place (a padded
    row's slot index is clipped, as ``_load_rows`` clipped it: with dt
    = 0 throughout it hands back the state it was given, and its
    clipped slot gets back what it held).  Through a view with H and P
    merged: written in five dimensions, the compiler gave the POOL the
    layout its update came in (P before H) and copied all of it in and
    out of every prefill of one chunk (1.2 GB of temporaries at the
    cell's sizes; my compile for a v5e, PR 31).  Merged, only the update
    is re-laid."""
    n_layers, n_slots, h, p, n = ssm_pool.shape
    flat = ssm_pool.reshape(n_layers, n_slots, h * p, n)
    states = states.reshape(-1, 1, 1, h * p, n).astype(flat.dtype)

    def row(i, pool):
        at = (layer, jnp.clip(slots[i], 0, n_slots - 1), 0, 0)
        return jax.lax.dynamic_update_slice(pool, states[i], at)

    flat = jax.lax.fori_loop(0, slots.shape[0], row, flat)
    return flat.reshape(ssm_pool.shape)


class Mamba2Mixer(nn.Module):
    cfg: GraniteConfig

    @nn.compact
    def __call__(self, hid, cache=None):
        """hid [B, T, d] -> [B, T, d]; with ``cache`` ({"conv", "ssm",
        "layer", "slots", "positions"}: the WHOLE state pool and this
        mixer's layer in it) returns (out, (conv, ssm)) with each row's
        slot updated."""
        cfg = self.cfg
        b, t, _ = hid.shape
        h, p, n = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
        di, dc, kw = cfg.d_inner, cfg.conv_dim, cfg.mamba_d_conv
        init = nn.initializers.normal(0.02)
        f32 = jnp.float32
        with jax.named_scope("ssm.in_proj"):
            proj = nn.Dense(di + dc + h, use_bias=False, dtype=cfg.dtype,
                            kernel_init=init, name="in_proj")(hid)
            z, xbc, dt = jnp.split(proj, [di, di + dc], axis=-1)
        conv_w = self.param("conv_w", init, (kw, dc), f32)
        conv_b = self.param("conv_b", nn.initializers.zeros, (dc,), f32)
        a_log = self.param("A_log", nn.initializers.zeros, (h,), f32)
        d_skip = self.param("D", nn.initializers.ones, (h,), f32)
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (h,), f32)

        valid = fresh = window = ssm_pool = None
        if cache is not None:
            valid = cache["positions"] >= 0                    # [B, T]
            fresh = cache["positions"][:, 0] == 0              # [B]
            ssm_pool, layer, slots = (cache["ssm"], cache["layer"],
                                      cache["slots"])
            window = (cache["conv"], layer, slots, fresh, valid)
        with jax.named_scope("ssm.conv"):
            xbc_act, conv_pool = slot_conv(xbc, conv_w, window,
                                           bias=conv_b, act=nn.silu)
        xs, b_mat, c_mat = jnp.split(xbc_act, [di, di + n], axis=-1)
        xs = xs.reshape(b, t, h, p)
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias)         # [B,T,H]
        if valid is not None:
            dt = jnp.where(valid[..., None], dt, 0.0)
        a = -jnp.exp(a_log)
        if cache is not None and t == 1:
            with jax.named_scope("ssm.step"):
                y, ssm_pool = _step_rows(
                    ssm_pool, layer, slots, fresh, dt[:, 0], a, xs[:, 0],
                    b_mat[:, 0], c_mat[:, 0])
                y = y[:, None]
        else:
            with jax.named_scope("ssm.scan"):
                s_in = None
                if cache is not None:
                    s_in = jnp.where(
                        fresh[:, None, None, None], 0.0,
                        _load_rows(ssm_pool, layer, slots).astype(f32))
                y, s_out = ssd_scan(xs, dt, a, b_mat, c_mat,
                                    cfg.mamba_chunk, s_in)
                if cache is not None:
                    ssm_pool = _store_rows(ssm_pool, layer, slots, s_out)
        with jax.named_scope("ssm.gate_norm"):
            y = y + d_skip[:, None] * xs
            y = y.reshape(b, t, di) * nn.silu(z.astype(f32))
            y = RMSNorm(cfg.rms_eps, cfg.dtype, name="norm")(y)
        with jax.named_scope("ssm.out_proj"):
            out = nn.Dense(cfg.d_model, use_bias=False, dtype=cfg.dtype,
                           kernel_init=init, name="out_proj")(y)
        return out if cache is None else (out, (conv_pool, ssm_pool))


class Granite(Decoder):
    """``models/decoder.py Decoder`` over a GraniteConfig, the contract
    of GPT2.__call__ with one more kind of cache: ``k_pages`` /
    ``v_pages`` [attention layers, pages, page, h_kv*d]; ``conv``
    [state-space layers, slots, d_conv-1, conv_dim], ``ssm`` [state-space
    layers, slots, H, P, N] float32 and ``slots`` [B] (each row's slot;
    outside the pool: a padded row); all carried whole through the
    layers."""


MIXERS = {
    MAMBA: Mixer(
        Mamba2Mixer, "mamba", ("conv", "ssm"), lambda cfg: {
            "conv_shape": (cfg.mamba_d_conv - 1, cfg.conv_dim),
            "ssm_shape": (cfg.mamba_n_heads, cfg.mamba_d_head,
                          cfg.mamba_d_state)}),
    # no position encoding; the scores scaled by ``attention_multiplier``
    ATTENTION: attention_kind(lambda cfg, name: Attention(
        cfg, rope=False, scale=cfg.attention_multiplier, name=name)),
}


# ------------------------------------------------------ init, loss, rules

@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _normal_leaf(key, shape, std: float, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def _special_leaf(cfg: GraniteConfig, name: str, key, shape):
    """The mixer's leaves that are not normal(0, 0.02): None for the
    others."""
    leaf = name.rsplit("/", 1)[-1]
    if leaf == "embed":     # so that embedding_multiplier x E has std 0.02
        return _normal_leaf(key, shape, 0.02 / cfg.embedding_multiplier,
                            jnp.dtype(cfg.param_dtype))
    if name.endswith(("wq/kernel", "wk/kernel")):
        # q and k each larger by sqrt(1/sqrt(d) / attention_multiplier),
        # so that the scores have the spread 1/sqrt(d) gives at 0.02
        grow = (cfg.head_dim ** -0.5 / cfg.attention_multiplier) ** 0.5
        return _normal_leaf(key, shape, 0.02 * grow,
                            jnp.dtype(cfg.param_dtype))
    if leaf == "A_log":
        return jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))
    if leaf == "D":
        return jnp.ones(shape, jnp.float32)
    if leaf == "dt_bias":   # inverse softplus of a step in [1e-3, 1e-1]
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                     * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
        return dt + jnp.log(-jnp.expm1(-dt))
    if leaf in ("conv_w", "conv_b"):    # PyTorch's depthwise default
        bound = cfg.mamba_d_conv ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    return None


def granite_init(cfg: GraniteConfig, rng):
    """The weights from the seed, leaf by leaf as ``llama_init`` makes
    them (shapes by ``eval_shape``; each leaf float32 from a key folded
    from its path, cast to ``cfg.param_dtype``): matrices normal(0,
    0.02), norm scales 1, the tied embedding normal(0, 0.02 /
    ``embedding_multiplier``) (at 0.02 the tied head makes every
    position's largest logit its own input token by a wide margin, and
    greedy decoding repeats the prompt's last token whatever the layers
    compute), ``wq`` and ``wk`` normal(0, 0.02 x (1/sqrt(d) /
    ``attention_multiplier``)^(1/2)) (0.0673 at the published sizes: at
    0.02 the scores ``attention_multiplier x q k^T`` have a standard
    deviation of 0.14, every softmax is flat, and attention is an average
    over the past that neither a wrong scale nor a position encoding nor
    a wrong page can move), and the mixer's own: ``A_log = log(1..H)``,
    ``D = 1``, ``dt_bias`` the inverse softplus of a step log-uniform in
    [0.001, 0.1], the conv's weight and bias uniform in +-1/sqrt(taps);
    those four stay float32 (they feed ``dt`` and the decay)."""
    return init_by_leaf(Granite, cfg, rng,
                        functools.partial(_special_leaf, cfg))


# (the source's config names no router loss coefficient, so there is none)
granite_loss_fn = functools.partial(next_token_loss, Granite)


def granite_partition_rules():
    """``models/decoder.py decoder_rules`` after the mixer's own: its
    projections a column- then a row-parallel pair, its small leaves
    whole."""
    return decoder_rules(
        (r"in_proj/kernel$", PS("fsdp", "tensor")),
        (r"out_proj/kernel$", PS("tensor", "fsdp")),
        (r"(conv_w|conv_b|A_log|D|dt_bias)$", PS()))
