"""Kimi-Linear family (HF ``model_type`` kimi_linear; moonshotai's
Kimi-Linear-48B-A3B) — layers of two kinds in one model: Kimi Delta
Attention (KDA: a gated delta rule whose decay is per key CHANNEL and
whose state is a ``d_k x d_v`` matrix a head) and, after every three of
them, multi-head LATENT attention with NO position encoding
(``models/kimi.py MLAttention`` with ``q_lora_rank`` None and
``mla_use_nope``); the FFN a dense SwiGLU in the first ``n_dense_layers``
and after them a shared expert beside experts routed by sigmoid scores and
a selection bias (``models/decoder.py ffn``, ``ops/moe.py``).  No bias
anywhere; the head is untied.

Layer ``l``: ``h = x + mixer_l(RMSNorm(x))``; ``y = h + ffn_l(RMSNorm(h))``.
After the last layer one more RMSNorm, then the head.

The KDA mixer on ``u`` [T, d], ``H`` heads of ``d_k = d_v``:

- ``q^ = u W_q``, ``k^ = u W_k``, ``v^ = u W_v`` [T, H d_k] each; each
  through its own depthwise causal convolution of ``kda_conv`` = 4 taps
  and SiLU (the three side by side are one convolution over ``3 H d_k``
  channels: ``conv_w`` [taps, 3 H d_k], ``models/layers.py slot_conv``);
- per head ``q = q' / max(|q'|, 1e-6) * d_k ** -0.5``, ``k = k' / max(|k'|,
  1e-6)``;
- the decay, per key channel: ``g = -exp(A_log[h]) * softplus((u W_fa) W_fb
  + dt_bias)`` <= 0 in float32, ``a = exp(g)``; ``beta = sigmoid(u W_b)``;
- the state ``S`` [d_k, d_v] a head, float32, from zeros: ``S~ = diag(a_t)
  S_{t-1}``; ``S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T``; ``o_t = S_t^T q_t``;
- ``y = RMSNorm_{d_k}(o_t) * sigmoid((u W_ga) W_gb)`` (one learned scale of
  ``d_k`` shared by the heads); ``y W_o``.

A forward over many positions (training, the full forward, the engine's
prefill) computes the recurrence in chunks (``kda_scan``, scope
``kda.scan``); a decode step is the recurrence once, each running row's
state updated where it lies (``kda_step``, scope ``kda.step``: on the chip
the Pallas kernel ``ops/delta_rule.py``), and the windows' slab BY SLOT
(``step_conv``; a prefill's and the trainer's convolution is
``slot_conv``).

With a cache this is the first family with BOTH a latent pool and a state
pool (``models.CacheSpec``: ``latent_dim`` > 0 and ``state_layers`` > 0):
``latent_pages`` [MLA layers, pages, page, row] for the latent layers;
``conv`` [KDA layers, slots, taps - 1, 3 H d_k] (the three windows, the
model's dtype) and ``ssm`` [KDA layers, slots, H, d_k, d_v] (float32) for
the mixers, with ``slots`` [B]; each layer indexes ITS pool by its number
among its kind, and all three arrays are carried whole through the layers.
A position < 0 is padding: there ``a = 1`` and ``beta = 0``, which makes
the recurrence the identity, and the window is taken at the last real
position (``slot_conv``); a row whose slot lies outside the pool (a padded
decode row) changes nothing.  A forward whose first position is 0 starts
from a zero state and a zero window whatever its slot held.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as PS

from .decoder import Decoder, Mixer, decoder_rules, next_token_loss
from .kimi import EXPERT_BIAS_STD, MLA, MLA_KIND, mla_params, routed_experts
from .layers import RMSNorm, init_by_leaf, slot_conv

KDA = "kda"
L2_EPS = 1e-6               # under the L2 norms of q and k


@dataclass(frozen=True)
class KimiLinearConfig:
    """moonshotai/Kimi-Linear-48B-A3B-Instruct as published (the
    defaults): 27 layers of 2304, latent attention at (1-indexed) 4, 8,
    ..., 24 and 27, KDA elsewhere (32 heads of 128, 4 taps); MLA of 32
    heads over rank 512 and widths 128 | 64 | 128 with no rotation and no
    query rank; layer 1 a dense SwiGLU of 9216, layers 2-27 a shared
    expert and top-8 of 256 experts of width 1024."""
    vocab_size: int = 163840
    layer_types: Tuple[str, ...] = tuple(
        MLA if (i % 4 == 3 and i < 24) or i == 26 else KDA
        for i in range(27))
    d_model: int = 2304
    kda_heads: int = 32
    kda_head_dim: int = 128
    kda_conv: int = 4
    kda_gate_rank: int = 128            # of the decay's and the output gate's
    # kda_scan's chunk, chosen INSIDE a forward of three KDA layers at these
    # widths (examples/probes/delta_scan_probe.py --layers 3, my chip runs,
    # PR 57; ms a forward at 1,024 | 2,048 positions): chunks of 32 9.33 |
    # 21.80, of 64 10.63 | 24.35; the form before PR 57 (a triangular solve
    # a chunk of 64, --tree a checkout of 0b5bcbc) 13.26 | 30.85.  One
    # layer's scan ALONE pays layout copies that a program's neighbours
    # absorb and orders the chunks otherwise (32 heads of 128 x 128, ms at
    # 1,024 | 2,048 | 4,096, before PR 57 and then with PR 57's first form of
    # the inverse: chunks of 32 1.85 | 3.63 | 7.52 and 1.75 | 3.50 | 8.06; of
    # 64 3.17 | 7.14 | 10.78 and 1.93 | 4.65 | 8.56; of 128 4.91 | 10.17 |
    # 20.17 and 3.07 | 6.99 | 14.29; as committed 3.85 at 2,048 in chunks of
    # 32, 4.91 of 64): ``_decayed_scores``' pairwise blocks grow with the
    # chunk.
    kda_chunk: int = 32
    kda_sub_chunk: int = 16             # and the blocks inside it
    n_head: int = 32                    # the latent layers'
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_use_nope: bool = True
    d_ff: int = 9216                    # the dense layers' width
    n_dense_layers: int = 1
    moe_d_ff: int = 1024                # one expert's width
    n_experts: int = 256                # what the router scores
    experts_per_token: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.446
    # The share of the experts held here (ops/moe.py); None: all.
    first_expert: int = 0
    held_experts: Optional[int] = None
    max_seq: int = 1048576
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    attn_impl: str = "dense"
    remat: bool = True
    mesh: Any = None

    def __post_init__(self):
        bad = set(self.layer_types) - {KDA, MLA}
        if bad:
            raise ValueError(f"layer_types holds {sorted(bad)}")
        if not self.mla_use_nope:
            raise ValueError("the latent layers take no position "
                             "encoding (mla_use_nope); a rotation has no "
                             "theta here")
        if self.kda_chunk % self.kda_sub_chunk:
            raise ValueError("kda_chunk must be whole kda_sub_chunks")

    @staticmethod
    def tiny(**overrides) -> "KimiLinearConfig":
        """The shape at a test's size: [kda (dense FFN), kda, kda, mla,
        kda, mla]: the dense layer inside a whole period, then a KDA and
        the closing latent layer; 64 wide, 4 KDA heads of 16 with gates
        of rank 8 and chunks of 8 in blocks of 4, 4 latent heads over
        rank 24 and widths 16 | 8 | 16, a dense FFN of 96, a shared
        expert and top-2 of 8 experts of width 32."""
        return KimiLinearConfig(**{**dict(
            vocab_size=256, layer_types=(KDA, KDA, KDA, MLA, KDA, MLA),
            d_model=64, kda_heads=4, kda_head_dim=16, kda_gate_rank=8,
            kda_chunk=8, kda_sub_chunk=4, n_head=4, kv_lora_rank=24,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            d_ff=96, moe_d_ff=32, n_experts=8, experts_per_token=2,
            max_seq=128, dtype=jnp.float32, param_dtype=jnp.float32),
            **overrides})

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def n_moe_layers(self) -> int:
        return max(self.n_layer - self.n_dense_layers, 0)

    @property
    def kda_dim(self) -> int:
        return self.kda_heads * self.kda_head_dim

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        return self.qk_head_dim ** -0.5

    def layers_of(self, kind: str) -> int:
        return sum(t == kind for t in self.layer_types)

    def mixer_params(self) -> int:
        """One KDA layer's mixer (``wq``, ``wk``, ``wv``, ``wo``, the two
        low-rank gates, ``wb`` and the taps), in parameters."""
        d, di, r = self.d_model, self.kda_dim, self.kda_gate_rank
        return 4 * d * di + 2 * (d * r + r * di) + d * self.kda_heads \
            + self.kda_conv * 3 * di

    def attention_params(self) -> int:
        """One latent layer's four matrices, in parameters."""
        return mla_params(self)

    # What ``models/decoder.py`` reads besides the fields: the kinds, and
    # the sparse layers' FFN (Kimi-K2's).
    @property
    def mixers(self):
        return MIXERS

    experts = property(routed_experts)
    shared_d_ff = property(lambda self: self.moe_d_ff
                           * self.n_shared_experts)


# ------------------------------------------------------- the delta rule

def _decayed_scores(left, k, gcum, sub: int):
    """``sum_c left[i, c] k[j, c] exp(G[i, c] - G[j, c])`` for ``i >= j``
    and 0 above the diagonal; left, k, gcum [..., C, D] (``gcum`` the
    cumulative log-decay inside the chunk, falling) -> [..., C, C].

    ``exp(G_i) * exp(-G_j)`` overflows float32 once a chunk's decay passes
    e^-88, so only DIFFERENCES ``G_i - G_j <= 0`` are ever exponentiated:
    the chunk is cut into blocks of ``sub`` rows; a block against itself
    takes the pairwise differences ([sub, sub, D], exact whatever the
    decay); a block I against an EARLIER block J goes through a reference
    point, the first row r of I: ``exp(G_i - G_r)`` (i in I) and ``exp(G_r
    - G_j)`` (j before r) are both <= 1, and the two sides meet in one
    matmul."""
    *lead, c, d = k.shape
    ns = c // sub
    lb, kb, gb = (z.reshape(*lead, ns, sub, d) for z in (left, k, gcum))
    # a masked entry's exponent may be positive: mask the exponent
    tri = jnp.tril(jnp.ones((sub, sub), bool))[..., None]
    pair = jnp.exp(jnp.where(
        tri, gb[..., :, None, :] - gb[..., None, :, :], -jnp.inf))
    diag = jnp.sum(lb[..., :, None, :] * kb[..., None, :, :] * pair,
                   axis=-1)                                # [.., ns, s, s]
    ref = gb[..., :1, :]                                   # [.., ns, 1, D]
    lq = lb * jnp.exp(gb - ref)
    kd = k[..., None, :, :] * jnp.exp(
        jnp.minimum(ref - gcum[..., None, :, :], 0.0))     # [.., ns, C, D]
    off = jnp.einsum("...id,...jd->...ij", lq, kd).reshape(*lead, c, c)
    block = jnp.arange(c) // sub
    off = jnp.where(block[:, None] > block[None, :], off, 0.0)
    eye = jnp.eye(ns, dtype=diag.dtype)
    return off + jnp.einsum("...aij,ab->...aibj", diag, eye).reshape(
        *lead, c, c)


def _scalar_decayed(gcum):
    """``exp(G_i - G_j)`` for ``i >= j`` and 0 above the diagonal, for a
    decay of ONE number a head: gcum [..., C] -> [..., C, C].  With a
    scalar the decay leaves the sum over the channels, so a chunk's scores
    are plain matmuls times this matrix and ``_decayed_scores``' blocks are
    not paid for; only differences <= 0 are exponentiated."""
    c = gcum.shape[-1]
    tri = jnp.tril(jnp.ones((c, c), bool))
    return jnp.exp(jnp.where(tri, gcum[..., :, None] - gcum[..., None, :],
                             -jnp.inf))


def _nilpotent_inverse(n):
    """``(I + N)^-1`` for strictly lower-triangular ``N`` [..., s, s]: ``(I -
    N)(I + N^2)(I + N^4) ..`` up to the last power below ``s``, exact because
    ``N^s = 0``.  The products are float32 multiplies and sums over every
    block at once, no matmul: nothing rounds an operand."""
    def times(x, y):
        return jnp.sum(x[..., :, :, None] * y[..., None, :, :], axis=-2)

    s = n.shape[-1]
    inv, power, reach = jnp.eye(s, dtype=n.dtype) - n, n, 2
    while reach < s:                    # power: N^(reach / 2)
        power = times(power, power)
        inv = inv + times(inv, power)
        reach *= 2
    return inv


def _unit_lower_inverse(a, block: int):
    """``(I + A)^-1`` for strictly lower-triangular ``A`` [..., C, C] (C whole
    blocks), exact in finitely many products and with no triangular solve:
    the diagonal blocks of ``block`` rows by ``_nilpotent_inverse``, then
    blocks joined two by two, ``inv([[P, 0], [R, Q]]) = [[P', 0], [-Q' R P',
    Q']]``, doubling until one block is the chunk.  A level is two matmuls
    over the whole ``[C, C]`` arrays, ``T <- (I - T R) T`` with ``T`` the
    block-diagonal of the inverses so far and ``R`` the blocks of ``A`` that
    join each pair: written so, a level's result feeds matmuls only.  An odd
    count of blocks leaves the last one unpaired until a later level."""
    *lead, c, _ = a.shape
    nb = c // block
    own = jnp.eye(nb, dtype=bool)[:, None, :, None]    # a block's own columns
    diag = jnp.sum(jnp.where(own, a.reshape(*lead, nb, block, nb, block),
                             0.0), axis=-2)            # [.., nb, s, s]
    t = jnp.where(own, _nilpotent_inverse(diag)[..., None, :],
                  0.0).reshape(a.shape)
    eye = jnp.eye(c, dtype=a.dtype)
    of, width = np.arange(c), block
    while width < c:
        b = of // width
        pair = (b[:, None] % 2 == 1) & (b[None, :] == b[:, None] - 1)
        t = (eye - t @ jnp.where(pair, a, 0.0)) @ t
        width *= 2
    return t


# (``jax.jit``: a program traces and lowers the scan once, not once a layer:
# the lowered module of Olmo-Hybrid's 16-layer prefill is 441 thousand
# characters with it and 918 without, the form before PR 57 578, and every
# warm start of a program pays for its size, PERF.md PR 57)
@functools.partial(jax.jit, static_argnums=(5, 6))
def kda_scan(q, k, v, g, beta, chunk: int, sub: int, state=None):
    """The gated delta rule over T positions in chunks.

    q, k [B, T, H, D_k], v [B, T, H, D_v] (q scaled, q and k normalised),
    g <= 0 the log of the decay, [B, T, H, D_k] (a key channel's) or
    [B, T, H, 1] (ONE a head), beta [B, T, H], all float32; a padded
    position has g = 0 and beta = 0 (the identity).  ``state`` [B, H, D_k,
    D_v] (None: zeros).  Returns (o [B, T, H, D_v], the state after the
    last position).

    Inside a chunk, with ``G`` the cumulative sum of g and ``Gamma =
    exp(G)``: ``A_ij = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)`` below the
    diagonal; ``T = (I + A)^-1`` (``_unit_lower_inverse``, from diagonal
    blocks of ``sub`` rows: each row's correction takes the rows before
    it); ``[W | U] = T diag(beta) [K * Gamma | V]``; with the incoming
    state ``S``: ``V' = U - W S``; ``O = (Q * Gamma) S + tril((Q
    K^T)_decayed) V'``; ``S' = diag(Gamma_C) S + (K * Gamma_C / Gamma)^T
    V'``.  Everything that does not read ``S`` is a
    matmul over all chunks at once: with ``qk`` the decayed ``Q K^T`` and
    ``K_out = K * Gamma_C / Gamma``, ``Q~ = Q * Gamma - qk W``, ``P = qk
    U``, ``M = K_out^T W`` and ``B = K_out^T U``; the pass over the chunks
    is ``O_c = Q~_c S + P_c; S <- Gamma_C S - M_c S + B_c``: it carries the
    state and two matmuls that do not wait for each other.  (The decay stays
    a float32 multiply beside ``M``: inside it, it would be rounded with the
    matmul's operands once a chunk.)  No ``1 / Gamma`` is formed:
    ``_decayed_scores`` says how, and for a scalar decay
    ``_scalar_decayed``, whose ``G`` is kept [.., C] with no axis of one
    behind it (the chip lays such an axis out as whole tiles).  T is filled
    up to whole chunks with identity positions; a T shorter than a chunk is
    one chunk of whole blocks."""
    bsz, t, h, d = q.shape
    if t < chunk:
        chunk = -(-t // sub) * sub
    fill = -t % chunk
    if fill:
        q, k, v, g, beta = (
            jnp.pad(z, [(0, 0), (0, fill)] + [(0, 0)] * (z.ndim - 2))
            for z in (q, k, v, g, beta))
    nc = (t + fill) // chunk

    def chunks(z):      # [B, nc*C, H, ...] -> [B, nc, H, C, ...]
        return jnp.moveaxis(
            z.reshape((bsz, nc, chunk) + z.shape[2:]), 3, 2)

    q, k, v = (chunks(z) for z in (q, k, v))
    beta = chunks(beta)[..., None]                         # [B,nc,H,C,1]
    if g.shape[-1] == 1 < d:            # the decay a head, not a channel
        gcum = jnp.cumsum(chunks(g[..., 0]), axis=-1)      # [B,nc,H,C]
        decayed = _scalar_decayed(gcum)
        to_end = jnp.exp(gcum[..., -1:] - gcum)[..., None]
        gamma = jnp.exp(gcum)[..., None]                   # [B,nc,H,C,1]

        def scores(left):
            return jnp.einsum("...id,...jd->...ij", left, k) * decayed
    else:
        gcum = jnp.cumsum(chunks(g), axis=-2)              # [B,nc,H,C,D]
        to_end = jnp.exp(gcum[..., -1:, :] - gcum)
        gamma = jnp.exp(gcum)

        def scores(left):
            return _decayed_scores(left, k, gcum, sub)
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    a_mat = jnp.where(strict, scores(k), 0.0) * beta
    t_mat = _unit_lower_inverse(a_mat, sub)
    wu = t_mat @ (beta * jnp.concatenate([k * gamma, v], axis=-1))
    w, u = wu[..., :d], wu[..., d:]
    qk = scores(q)
    q_in = q * gamma - qk @ w                              # Q~
    p = qk @ u
    k_out = k * to_end
    m = jnp.einsum("...ck,...cj->...kj", k_out, w)         # [B,nc,H,D,D]
    b = jnp.einsum("...ck,...cv->...kv", k_out, u)         # [B,nc,H,D,Dv]
    g_out = jnp.swapaxes(gamma[..., -1:, :], -1, -2)       # [B,nc,H,D|1,1]

    def one(s, blk):
        q_c, p_c, m_c, b_c, g_c = blk
        o = jnp.einsum("bhck,bhkv->bhcv", q_c, s) + p_c
        s = g_c * s - jnp.einsum("bhkj,bhjv->bhkv", m_c, s) + b_c
        return s, o

    if state is None:
        state = jnp.zeros((bsz, h, d, v.shape[-1]), jnp.float32)
    state, o = jax.lax.scan(
        one, state, tuple(jnp.moveaxis(z, 1, 0)
                          for z in (q_in, p, m, b, g_out)))
    o = jnp.moveaxis(o, 0, 1)                              # [B,nc,H,C,Dv]
    o = jnp.moveaxis(o, 2, 3).reshape(bsz, nc * chunk, h, v.shape[-1])
    return o[:, :t], state


def _step_kernel(pool, q) -> bool:
    """Whether a decode step's recurrence takes the Pallas kernel: by the
    pool's shape and dtype and the backend, nothing else."""
    from ..ops import delta_rule

    return jax.default_backend() == "tpu" and delta_rule.supported(pool, q)


def kda_step(pool, layer, slots, fresh, q, k, v, a, beta):
    """The recurrence once for every row of a decode batch, where the
    states lie: q, k [B, H, D_k], v [B, H, D_v], a [B, H, D_k] (the decay a
    key channel) or [B, H, 1] (one a head) float32, beta [B, H]; ``fresh``
    [B]: the row starts from zeros; layer ``layer`` of the WHOLE pool [L,
    slots, H / pack, D_k, pack * D_v] (``ops/delta_rule.py state_shape``:
    ``pack`` heads' values side by side where one head's are no whole
    tiles; 1 otherwise).  A row whose slot lies outside the pool (a padded
    row) changes nothing and gives zeros; live rows have distinct slots.
    Multiplies and sums in float32: no matmul rounds the state.  Returns
    (o [B, H, D_v], the pool).

    On the ``tpu`` backend, a float32 pool of whole tiles goes
    through ``ops/delta_rule.py kda_step``: each running row's state once
    in and once out, no other slot touched.  Any other pool is worked BY
    SLOT in ``jnp``, the kernel's plain definition: the rows' small vectors
    are put in slot order (a one-hot sum over the rows: a slot no row
    names, and a padded row, leave ``a = 1``, ``beta = 0``: the identity),
    and the layer is decayed and corrected as one slab, written back over
    itself (three passes over the slab on the chip where the mathematics
    needs two; gathered by row instead, the states moved seven times
    their bytes in loops over the rows: my compiles for a v5e, PR 41)."""
    from ..ops import delta_rule

    if _step_kernel(pool, q):
        return delta_rule.kda_step(pool, layer, slots, fresh, q, k, v, a,
                                   beta)
    n_slots = pool.shape[1]
    hit = slots[:, None] == jnp.arange(n_slots)[None, :]       # [B, S]

    def by_slot(x, idle=0.0):       # [B, ...] -> [S, ...]
        m = hit.reshape(hit.shape + (1,) * (x.ndim - 1))
        put = jnp.sum(jnp.where(m, x[:, None], 0.0), axis=0)
        live = jnp.any(hit, axis=0).reshape((n_slots,) + (1,) * (x.ndim - 1))
        return jnp.where(live, put, idle)

    q, k, v, beta = (by_slot(x) for x in (q, k, v, beta))
    a = by_slot(a, 1.0)
    keep = 1.0 - by_slot(fresh.astype(jnp.float32))            # [S]
    s = delta_rule.unpack_states(pool[layer], q.shape[1]).astype(
        jnp.float32) * keep[:, None, None, None]
    s = a[..., None] * s                                   # S~
    err = v - jnp.sum(s * k[..., None], axis=-2)           # v - S~^T k
    s = s + (beta[..., None] * k)[..., None] * err[..., None, :]
    o = jnp.sum(s * q[..., None], axis=-2)                     # [S, H, D]
    o = jnp.sum(jnp.where(hit[:, :, None, None], o[None], 0.0), axis=1)
    return o, pool.at[layer].set(
        delta_rule.pack_states(s, pool.shape[2]).astype(pool.dtype))


def step_conv(x, taps, window, act):
    """``slot_conv`` for a decode step (x [B, 1, C]), BY SLOT as
    ``kda_step``'s ``jnp`` form is: each row's last K-1 inputs are read
    from its slot, the window slides by one (a padded row's stays), and
    the layer's slots are rewritten as ONE slab, each from the row that
    names it (a one-hot sum over the rows; a slot no row names keeps what
    it held).  Row by row
    (``slot_conv``: a slice at each row's length, a scatter) each was a
    loop over the batch on the chip, 16 x ~8 operations a layer, and with
    20 layers the profiler's capture of a step did not end (PR 41)."""
    pool, layer, slots, fresh, valid = window
    b, _, c = x.shape
    kw = taps.shape[0]
    flat = pool.reshape(pool.shape[:2] + (-1,))
    before = jnp.where(fresh[:, None, None], 0,
                       flat[layer, slots].reshape(b, kw - 1, c))
    past = jnp.concatenate([before.astype(x.dtype), x], axis=1)
    out = act(sum(past[:, i:i + 1].astype(jnp.float32) * taps[i]
                  for i in range(kw)))
    keep = jnp.where(valid[:, :, None], past[:, 1:], past[:, :-1])
    keep = keep.reshape(b, -1).astype(flat.dtype)
    hit = slots[:, None] == jnp.arange(flat.shape[1])[None, :]     # [B, S]
    put = jnp.sum(jnp.where(hit[:, :, None], keep[:, None], 0), axis=0)
    slab = jnp.where(jnp.any(hit, axis=0)[:, None], put.astype(flat.dtype),
                     flat[layer])
    return out, flat.at[layer].set(slab).reshape(pool.shape)


def _l2_normalised(x):
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True),
                           L2_EPS)


def _load_states(pool, layer, slots):
    """Each row's slot of the layer as the pool holds it, float32."""
    return pool.at[layer, slots].get(mode="clip").astype(jnp.float32)


def load_states(pool, layer, slots, fresh, heads: int):
    """Each row's state [B, H, D_k, D_v] for a scan: gathered from its slot
    of the pool (``kda_step`` has the layout; an index outside the pool is
    clipped: a padded row reads some other row's, and nothing is made of
    it), zeros for a ``fresh`` row."""
    from ..ops.delta_rule import unpack_states

    return jnp.where(
        fresh[:, None, None, None], 0.0,
        unpack_states(_load_states(pool, layer, slots), heads))


def store_states(pool, layer, slots, states):
    """The states a scan leaves [B, H, D_k, D_v], each into its row's slot
    (a slot outside the pool: dropped)."""
    from ..ops.delta_rule import pack_states

    return pool.at[layer, slots].set(
        pack_states(states, pool.shape[2]).astype(pool.dtype), mode="drop")


class KDAMixer(nn.Module):
    cfg: KimiLinearConfig

    @nn.compact
    def __call__(self, u, cache=None):
        """u [B, T, d] -> [B, T, d]; with ``cache`` ({"conv", "ssm",
        "layer", "slots", "positions"}: the WHOLE state pool and this
        mixer's layer in it) returns (out, (conv, ssm)) with each row's
        slot updated."""
        cfg = self.cfg
        b, t, _ = u.shape
        h, dk, di = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_dim
        f32 = jnp.float32
        init = nn.initializers.normal(0.02)
        dense = functools.partial(nn.Dense, use_bias=False,
                                  dtype=cfg.dtype, kernel_init=init)
        with jax.named_scope("kda.proj"):
            qkv = jnp.concatenate(
                [dense(di, name=name)(u) for name in ("wq", "wk", "wv")],
                axis=-1)
            f = dense(di, name="f_b")(dense(cfg.kda_gate_rank,
                                            name="f_a")(u))
            z = dense(di, name="g_b")(dense(cfg.kda_gate_rank,
                                            name="g_a")(u))
            b_logit = dense(h, name="wb")(u)
        conv_w = self.param("conv_w", init, (cfg.kda_conv, 3 * di), f32)
        a_log = self.param("A_log", nn.initializers.zeros, (h,), f32)
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (di,), f32)

        valid = fresh = window = ssm_pool = None
        if cache is not None:
            valid = cache["positions"] >= 0                    # [B, T]
            fresh = cache["positions"][:, 0] == 0              # [B]
            ssm_pool, layer, slots = (cache["ssm"], cache["layer"],
                                      cache["slots"])
            window = (cache["conv"], layer, slots, fresh, valid)
        with jax.named_scope("kda.conv"):
            if window is not None and t == 1:
                qkv, conv_pool = step_conv(qkv, conv_w, window, nn.silu)
            else:
                qkv, conv_pool = slot_conv(qkv, conv_w, window, act=nn.silu)
        with jax.named_scope("kda.gate"):
            q, k, v = (x.reshape(b, t, h, dk)
                       for x in jnp.split(qkv, 3, axis=-1))
            q, k = _l2_normalised(q) * dk ** -0.5, _l2_normalised(k)
            g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
                (f.astype(f32) + dt_bias).reshape(b, t, h, dk))
            beta = jax.nn.sigmoid(b_logit.astype(f32))         # [B,T,H]
            if valid is not None:    # padding: the identity
                g = jnp.where(valid[..., None, None], g, 0.0)
                beta = jnp.where(valid[..., None], beta, 0.0)
        if cache is not None and t == 1:
            with jax.named_scope("kda.step"):
                o, ssm_pool = kda_step(
                    ssm_pool, layer, slots, fresh, q[:, 0], k[:, 0],
                    v[:, 0], jnp.exp(g[:, 0]), beta[:, 0])
                o = o[:, None]
        else:
            with jax.named_scope("kda.scan"):
                s_in = None
                if cache is not None:
                    s_in = load_states(ssm_pool, layer, slots, fresh, h)
                o, s_out = kda_scan(q, k, v, g, beta, cfg.kda_chunk,
                                    cfg.kda_sub_chunk, s_in)
                if cache is not None:
                    ssm_pool = store_states(ssm_pool, layer, slots, s_out)
        with jax.named_scope("kda.out_norm"):
            y = RMSNorm(cfg.rms_eps, f32, name="o_norm")(o) \
                * jax.nn.sigmoid(z.astype(f32).reshape(b, t, h, dk))
            y = y.reshape(b, t, di).astype(cfg.dtype)
        with jax.named_scope("kda.out_proj"):
            out = dense(cfg.d_model, name="wo")(y)
        return out if cache is None else (out, (conv_pool, ssm_pool))


class KimiLinear(Decoder):
    """``models/decoder.py Decoder`` over a KimiLinearConfig: a step runs
    against BOTH caches (``kv_cache`` = {"latent_pages", "page_table",
    "conv", "ssm", "slots"}, ``positions`` [B, T]; the module docstring
    has the shapes)."""


MIXERS = {
    KDA: Mixer(KDAMixer, "kda", ("conv", "ssm"), lambda cfg: {
        "conv_shape": (cfg.kda_conv - 1, 3 * cfg.kda_dim),
        "ssm_shape": (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_head_dim)}),
    MLA: dataclasses.replace(MLA_KIND, norm="mixer_norm"),
}


# ------------------------------------------------------ init, loss, rules

def delta_rule_leaf(taps: int, name: str, key, shape):
    """The delta-rule mixers' leaves that are not normal(0, 0.02) (this
    family's and ``models/olmo_hybrid.py``'s): None for the others."""
    leaf = name.rsplit("/", 1)[-1]
    if leaf == "A_log":     # a head's rate, uniform in [1, 16]
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                          16.0))
    if leaf == "dt_bias":   # inverse softplus of a step in [1e-3, 1e-1]
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                     * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
        return dt + jnp.log(-jnp.expm1(-dt))
    if leaf == "conv_w":    # PyTorch's depthwise default, no bias
        bound = taps ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    return None


def _special_leaf(cfg: KimiLinearConfig, name: str, key, shape):
    """The leaves that are not normal(0, 0.02): None for the others."""
    if name.rsplit("/", 1)[-1] == "expert_bias":
        return EXPERT_BIAS_STD * jax.random.normal(key, shape, jnp.float32)
    return delta_rule_leaf(cfg.kda_conv, name, key, shape)


def kimi_linear_init(cfg: KimiLinearConfig, rng):
    """The weights from the seed, leaf by leaf (``models/layers.py
    init_by_leaf``): matrices, the embedding and the head normal(0,
    0.02), norm scales 1, ``expert_bias`` normal(0, ``EXPERT_BIAS_STD``)
    as Kimi-K2's, and the mixer's own, which stay float32 (they feed the
    decay): ``A_log = log(uniform(1, 16))`` a head, ``dt_bias`` the
    inverse softplus of a step log-uniform in [0.001, 0.1] a channel (so a
    token's log-decay lies in about -1.6 .. -0.001 and a chunk of 64 can
    pass e^-100), the taps uniform in +-1/sqrt(taps)."""
    return init_by_leaf(KimiLinear, cfg, rng,
                        functools.partial(_special_leaf, cfg))


# (the source balances its experts through the selection bias; no
# auxiliary loss has a weight in the published config)
kimi_linear_loss_fn = functools.partial(next_token_loss, KimiLinear)


def kimi_linear_partition_rules():
    """``models/decoder.py decoder_rules`` after the mixer's and the
    latent layers' own: the low-rank gates' first halves whole on
    ``tensor``, their second halves column-parallel into the heads (as
    ``wq`` / ``wk`` / ``wv`` are), the mixer's small leaves whole."""
    return decoder_rules(
        (r"(wkv_a|f_a|g_a|wb)/kernel$", PS("fsdp", None)),
        (r"(f_b|g_b)/kernel$", PS("fsdp", "tensor")),
        (r"wkv_b$", PS("fsdp", "tensor")),
        (r"(conv_w|A_log|dt_bias)$", PS()))
