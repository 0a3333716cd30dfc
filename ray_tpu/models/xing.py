"""Xing4.0 family (HF ``model_type`` xing4_0; XingChen-AGI's
Xing4.0-29B-A4B) — Kimi-K2's layer (``models/kimi.py``: latent attention,
a dense SwiGLU in the first ``n_dense_layers`` and after them a shared
expert beside experts routed by sigmoid scores and a selection bias) with
ONE thing changed: the residual path.  A token does not carry one vector
``x`` between sublayers but ``n = hc_mult`` of them, ``X`` in ``R^{n x
d}``, and every sublayer learns, per token, how to read its input from
them, how to write its output back and how to mix them:
manifold-constrained hyper-connections (mHC, arXiv:2512.24880, over
hyper-connections, arXiv:2409.19606).

For a sublayer ``F`` (attention or FFN; each has its own ``Phi``, biases,
gates and norm scale: the leaves ``attn_hc`` | ``mlp_hc`` of a layer):

1. ``r = RMSNorm(vec(X))`` over the flattened ``n * d`` numbers (float32,
   ``rms_eps``; ``vec`` is stream after stream).
2. ``[p | q | s] = r Phi``, ``Phi`` in ``R^{nd x (n + n + n^2)}``.
   ``H_pre = sigmoid(a_pre p + b_pre)`` in ``R^n``;
   ``H_post = 2 sigmoid(a_post q + b_post)`` in ``R^n``;
   ``S = clamp(a_res mat(s) + b_res, hc_clamp_min, hc_clamp_max)`` in
   ``R^{n x n}`` (``mat`` row by row: ``S[j, i] = s[j n + i]``), ``M_0 =
   exp(S)``, then ``hc_sinkhorn_iters`` times: every COLUMN divided by its
   sum + ``hc_eps``, then every ROW by its sum + ``hc_eps``; ``H_res`` is
   what is left, doubly stochastic to the iteration's error.  ``a_*`` are
   learned scalars (``map_gate``), ``b_*`` learned biases (``map_bias``).
   All of step 2 in float32 (scope ``hc.map``, with step 1).
3. ``u = sum_i H_pre[i] X_i`` (scope ``hc.pre``); ``y = F(RMSNorm_d(u))``,
   the block's own norm (``attn_norm`` | ``mlp_norm``) as in every family.
4. ``X'_j = sum_i H_res[j, i] X_i + H_post[j] y`` (scope ``hc.post``).

Entry: ``X_i = embed(token)`` for every ``i``.  Exit: ``x = sum_i X_i``,
then ``norm_f`` and the head.  The streams are held in ``cfg.dtype``
(bf16), the maps and the two mixes are computed in float32.

What the config does not place (entry, exit, columns before rows, where
``hc_eps`` and the clamp enter, ``mat``'s order, the init) is written
down as above and listed under ``assumed`` in the benchmark's
configuration (``benchmark/configs/xing4.0-29b-a4b.json``).

The source's multi-token prediction module (``num_nextn_predict_layers``
1) lies past the last layer and no served logit depends on it; it is not
written (ROADMAP Reach M4), and a config that asks for it is refused.

This file adds NO mixer and NO FFN: ``MLA_KIND``, ``MLAttention``,
``routed_experts`` and the shared expert are ``models/kimi.py``'s; the
latent pool, the engine and the kernels are used as they are (the streams
live inside a step: nothing new is cached).  It is the config, the
residual kind (``models/decoder.py Residual``: ``HC``), the init of its
leaves and their partition rules.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as PS

from .decoder import Decoder, Residual, next_token_loss
from .kimi import EXPERT_BIAS_STD, KimiK2Config, kimi_k2_partition_rules
from .layers import RMSNorm, init_by_leaf

# How init draws the maps' leaves (``xing_init`` has the argument).
HC_GATES = (0.4, 0.4, 0.125)        # a_pre, a_post, a_res
HC_BIAS_STD = (0.5, 0.5, 0.3)       # of b_pre, b_post, b_res
HC_RES_DIAG = 1.0                   # added to b_res's diagonal


@dataclass(frozen=True)
class XingConfig(KimiK2Config):
    """XingChen-AGI/Xing4.0-29B-A4B as published (the defaults): 40
    layers of 3584 carried as FOUR residual streams, 32 heads over ranks
    768 | 512 and head widths 128 | 64 | 128, layers 0-1 a dense SwiGLU of
    9216, layers 2-39 a shared expert and top-4 of 64 experts of width
    1024.  Kimi-K2's fields at this model's sizes, and the ``hc`` keys."""
    vocab_size: int = 131072
    n_layer: int = 40
    d_model: int = 3584
    n_head: int = 32
    q_lora_rank: Optional[int] = 768
    d_ff: int = 9216
    n_dense_layers: int = 2
    moe_d_ff: int = 1024
    n_experts: int = 64
    experts_per_token: int = 4
    routed_scaling_factor: float = 2.0
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    # Manifold-constrained hyper-connections.
    hc_mult: int = 4                    # n: the residual streams
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6                # beside every sum of the Sinkhorn
    hc_clamp_min: float = -30.0         # of S, before exp
    hc_clamp_max: float = 30.0
    # The source's draft module (multi-token prediction): not written.
    num_nextn_predict_layers: int = 0

    def __post_init__(self):
        super().__post_init__()
        if self.num_nextn_predict_layers:
            raise ValueError(
                "the multi-token prediction module is not written: no "
                "served logit depends on it (num_nextn_predict_layers "
                "must be 0)")
        if self.hc_mult < 1 or self.hc_sinkhorn_iters < 0:
            raise ValueError("hc_mult >= 1 and hc_sinkhorn_iters >= 0")

    @staticmethod
    def tiny(**overrides) -> "XingConfig":
        """The shape at a test's size: two dense layers and two sparse
        ones over four streams; Kimi-K2's tiny widths (64 wide, 4 heads
        over ranks 32 | 24 and widths 16 | 8 | 16, a dense FFN of 96, a
        shared expert and top-2 of 8 experts of width 32, YaRN over 32
        original positions)."""
        return XingConfig(**{**dict(
            vocab_size=256, n_layer=4, d_model=64, n_head=4,
            q_lora_rank=32, kv_lora_rank=24, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, d_ff=96, moe_d_ff=32,
            n_experts=8, experts_per_token=2, rope_factor=4.0,
            rope_original_max=32, max_seq=128, dtype=jnp.float32,
            param_dtype=jnp.float32), **overrides})

    # What ``models/decoder.py`` reads beside Kimi-K2's ``layer_types``,
    # ``mixers`` and ``experts``: the residual kind.
    @property
    def residual(self) -> Residual:
        return HC

    def hc_params(self) -> int:
        """One sublayer's maps, in parameters: ``Phi``, the flattened
        norm's scale, the biases and the three gates."""
        n = self.hc_mult
        cols = 2 * n + n * n
        return n * self.d_model * (cols + 1) + cols + 3


# ------------------------------------------------------ the residual kind

def sinkhorn(m, iters: int, eps: float):
    """``m`` [n, n, ...] positive (``m[j, i]`` over the tokens) -> the same
    after ``iters`` rounds of: every column ``i`` divided by its sum over
    ``j`` + ``eps``, then every row ``j`` by its sum over ``i`` + ``eps``.
    The two leading axes are the matrix, so the tokens lie along the
    lanes."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
    return m


def _begin(cfg, x):
    """x [B, T, d] -> X [B, T, n, d]: every stream the embedding."""
    b, t, d = x.shape
    with jax.named_scope("hc.begin"):
        return jnp.broadcast_to(x[:, :, None], (b, t, cfg.hc_mult, d))


def _end(cfg, x):
    """X [B, T, n, d] -> x [B, T, d]: the streams' sum."""
    with jax.named_scope("hc.end"):
        return jnp.sum(x.astype(jnp.float32), axis=2).astype(cfg.dtype)


class HyperConnection(nn.Module):
    """Steps 1-3 of one sublayer: X [B, T, n, d] -> (``u`` [B, T, d], the
    maps ``(H_post [n, B, T], H_res [n, n, B, T])`` step 4 needs,
    float32).  ``live`` [B, T] bool (None: every row): the rows whose
    ``H_res`` are counted in what it sows (``residual``: the largest ``|row
    sum - 1|`` and ``|column sum - 1|``)."""
    cfg: Any

    @nn.compact
    def __call__(self, x, live=None):
        cfg = self.cfg
        n, f32 = cfg.hc_mult, jnp.float32
        b, t, _, d = x.shape
        cols = 2 * n + n * n
        phi = self.param("phi", nn.initializers.normal(0.02),
                         (n * d, cols), f32)
        bias = self.param("map_bias", nn.initializers.zeros, (cols,), f32)
        gate = self.param("map_gate", nn.initializers.ones, (3,), f32)
        with jax.named_scope("hc.map"):
            r = RMSNorm(cfg.rms_eps, f32, name="norm")(
                x.reshape(b, t, n * d))
            # [cols, B, T]: a map's entry is an array over the tokens
            z = jnp.einsum("btk,kc->cbt", r, phi)
            of = np.repeat(np.arange(3), [n, n, n * n])  # a column's gate
            z = z * gate[of][:, None, None] + bias[:, None, None]
            h_pre = jax.nn.sigmoid(z[:n])                   # [n, B, T]
            h_post = 2.0 * jax.nn.sigmoid(z[n:2 * n])
            s = jnp.clip(z[2 * n:], cfg.hc_clamp_min, cfg.hc_clamp_max)
            h_res = sinkhorn(jnp.exp(s).reshape(n, n, b, t),
                             cfg.hc_sinkhorn_iters, cfg.hc_eps)

            def off_one(sums):      # the largest |sum - 1| of a live row
                off = jnp.max(jnp.abs(sums - 1.0), axis=0)
                return jnp.max(off if live is None
                               else jnp.where(live, off, 0.0))

            self.sow("intermediates", "residual", jnp.stack([
                off_one(jnp.sum(h_res, axis=1)),            # rows
                off_one(jnp.sum(h_res, axis=0))]))          # columns
        with jax.named_scope("hc.pre"):
            u = sum(h_pre[i][..., None] * x[:, :, i].astype(f32)
                    for i in range(n)).astype(cfg.dtype)
        return u, (h_post, h_res)


def _write(cfg, x, maps, y):
    """Step 4: X [B, T, n, d], the maps, y [B, T, d] -> X'."""
    h_post, h_res = maps
    n, f32 = cfg.hc_mult, jnp.float32
    with jax.named_scope("hc.post"):
        y = y.astype(f32)
        xs = [x[:, :, i].astype(f32) for i in range(n)]
        out = jnp.stack([
            sum(h_res[j, i][..., None] * xs[i] for i in range(n))
            + h_post[j][..., None] * y for j in range(n)],
            axis=2).astype(x.dtype)
    return out


# The residual kind: ``n`` streams a token, read and written through maps
# a sublayer computes from them.
HC = Residual(
    begin=_begin, read=HyperConnection, write=_write, end=_end,
    axes=("batch", "seq", "stream", "embed"), describe=lambda cfg: {
        "streams": cfg.hc_mult, "sublayers": 2 * cfg.n_layer,
        "sinkhorn_iters": cfg.hc_sinkhorn_iters})


class Xing(Decoder):
    """``models/decoder.py Decoder`` over a XingConfig: Kimi-K2's step
    against the latent pool, the layers joined by the residual kind."""


# ------------------------------------------------------ init, loss, rules

def _special_leaf(cfg: XingConfig, name: str, key, shape):
    leaf = name.rsplit("/", 1)[-1]
    n = cfg.hc_mult
    if leaf == "expert_bias":
        return EXPERT_BIAS_STD * jax.random.normal(key, shape, jnp.float32)
    if leaf == "phi":       # float32 whatever ``param_dtype``
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    if leaf == "map_gate":
        return jnp.asarray(HC_GATES, jnp.float32)
    if leaf == "map_bias":
        std = jnp.asarray(np.repeat(HC_BIAS_STD, [n, n, n * n]),
                          jnp.float32)
        diag = jnp.zeros(shape, jnp.float32).at[2 * n:].set(
            HC_RES_DIAG * jnp.eye(n, dtype=jnp.float32).reshape(-1))
        return std * jax.random.normal(key, shape, jnp.float32) + diag
    return None


def xing_init(cfg: XingConfig, rng):
    """The weights from the seed, leaf by leaf (``models/layers.py
    init_by_leaf``), Kimi-K2's draws (``models/kimi.py kimi_k2_init``)
    and the maps' leaves in float32: ``phi`` normal(0, 0.02) like every
    matrix, so that over the normed 14,336 numbers ``p``, ``q`` and ``s``
    have a standard deviation of 0.02 x sqrt(14336) = 2.39; the gates
    ``a_pre`` = ``a_post`` = 0.4 and ``a_res`` = 0.125, so the
    input-dependent part of a sigmoid's argument is ~1 wide (``H_pre``
    moves between ~0.27 and ~0.73 from token to token) and that of ``S``
    ~0.3; the biases normal(0, 0.5 | 0.5 | 0.3) with 1 added on ``b_res``'s
    diagonal.  Chosen, as ``expert_bias`` was, so that the comparison can
    fail: ``H_res`` is neither the identity (a diagonal of ~0.45) nor
    uniform, the input-dependent part moves an entry by up to ~0.3, and
    the Sinkhorn count matters (after ONE round a column sum is up to 0.31
    off 1, after 20 under 2e-6, over 4M drawn tokens); a wider ``S`` would
    leave 20 rounds short of 1e-4 in its tail (at 0.5 | 0.5: 1e-3)."""
    return init_by_leaf(Xing, cfg, rng, functools.partial(_special_leaf,
                                                          cfg))


xing_loss_fn = functools.partial(next_token_loss, Xing)


def xing_partition_rules():
    """Kimi-K2's rules after the maps' own: ``Phi``, the biases and the
    gates whole on every chip (a sublayer's are 0.35M numbers; the
    flattened norm's ``scale`` falls under the shared rule)."""
    return ((r"_hc/(phi|map_bias|map_gate)$", PS()),) \
        + kimi_k2_partition_rules()
