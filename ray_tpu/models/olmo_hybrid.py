"""Olmo-Hybrid family (HF ``model_type`` olmo_hybrid; allenai's
Olmo-Hybrid-7B) — a dense decoder whose layers are of two kinds: Gated
DeltaNet (``linear_attention``: a gated delta rule with ONE decay a head
whose state is a rectangular ``d_k x d_v`` matrix a head; Yang, Kautz,
Hatamizadeh, "Gated Delta Networks", 2024) and, after every three of them,
full multi-head attention (``full_attention``) with a QK-norm over the
whole width and NO position encoding; every FFN a dense SwiGLU.  No bias
anywhere; the head is untied.

Layer ``l`` norms each sublayer's OUTPUT (the OLMo 2 / OLMo 3 block,
``norm_output``): ``h = x + RMSNorm(mixer_l(x))``; ``y = h + RMSNorm(
mlp(h))``.  After the last layer one more RMSNorm, then the head.
``mlp(h) = W_down(silu(W_gate h) * W_up h)``.

``full_attention`` (``models/decoder.py gqa`` with ``qk_norm="width"``,
``rope=False``): ``q = RMSNorm_d(W_q x)``, ``k = RMSNorm_d(W_k x)``, ``v =
W_v x``; ``n_head`` heads of ``d / n_head``; causal softmax at ``head_dim **
-0.5``; ``W_o``.

``linear_attention`` (``GDNMixer``) on ``u`` [T, d], ``H`` heads of ``d_k``
keys and ``d_v`` values:

- ``q^ = u W_q``, ``k^ = u W_k`` [T, H d_k], ``v^ = u W_v`` [T, H d_v]; the
  three side by side through ONE depthwise causal convolution of ``K`` = 4
  taps over ``2 H d_k + H d_v`` channels, no bias, then SiLU (``conv_w`` [K,
  2 H d_k + H d_v], ``models/layers.py slot_conv``);
- per head ``q = q' / max(|q'|, 1e-6) * d_k ** -0.5``, ``k = k' / max(|k'|,
  1e-6)``;
- the decay, ONE number a head: ``g = -exp(A_log[h]) * softplus(u W_a +
  dt_bias[h])`` <= 0 in float32, ``a = exp(g)``; ``beta = 2 sigmoid(u
  W_b)`` in (0, 2) (``linear_allow_neg_eigval``: a step's ``I - beta k
  k^T`` may have the eigenvalue -1);
- the state ``S`` [d_k, d_v] a head, float32, from zeros: ``S~ = a_t
  S_{t-1}``; ``S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T``; ``o_t = S_t^T q_t``;
- ``y = RMSNorm_{d_v}(o_t) * silu(u W_g)`` (one learned scale of ``d_v``
  shared by the heads; the gate SiLU, where Kimi-Linear's is a sigmoid);
  ``y W_o``.

The delta rule is ``models/kimi_linear.py``'s, one implementation for both
families: ``kda_scan`` (a forward over many positions, scope ``gdn.scan``;
with a decay of one number a head a chunk's scores are plain matmuls times
``exp(G_i - G_j)``) and ``kda_step`` (a decode step, scope ``gdn.step``: on
the chip the Pallas kernel ``ops/delta_rule.py``), the windows' slab by
slot (``step_conv``).

With a cache this is a K/V pool beside a state pool (``models.CacheSpec``:
``kv_layers`` > 0 and ``state_layers`` > 0, as Granite's): ``k_pages`` /
``v_pages`` [attention layers, pages, page, n_kv_head * head_dim] for the
attention layers; ``conv`` [GDN layers, slots, K - 1, 2 H d_k + H d_v] (the
one window, the model's dtype) and ``ssm`` [GDN layers, slots, H / pack,
d_k, pack * d_v] (float32; ``ops/delta_rule.py state_shape``: 30 heads of
96 x 192 lie as 15 pairs of 96 x 384, whole tiles with no padding) for the
mixers, with ``slots`` [B].  A position < 0 is padding: there ``a = 1`` and
``beta = 0``, the identity, and the window is taken at the last real
position; a row whose slot lies outside the pool changes nothing.  A forward
whose first position is 0 starts from a zero state and a zero window
whatever its slot held.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

from .decoder import (Attention, Decoder, Mixer, attention_kind,
                      decoder_rules, next_token_loss)
from .kimi_linear import (L2_EPS, _l2_normalised,  # noqa: F401
                          delta_rule_leaf, kda_scan, kda_step, load_states,
                          step_conv, store_states)
from .layers import RMSNorm, init_by_leaf, slot_conv

GDN = "linear_attention"
ATTENTION = "full_attention"


@dataclass(frozen=True)
class OlmoHybridConfig:
    """allenai/Olmo-Hybrid-7B as published (the defaults): 32 layers of
    3840, three ``linear_attention`` (30 heads of 96 keys and 192 values, 4
    taps) then one ``full_attention`` (30 heads of 128, as many K/V heads),
    eight times; every FFN a SwiGLU of 11,008; vocabulary 100,352."""
    vocab_size: int = 100352
    layer_types: Tuple[str, ...] = (GDN, GDN, GDN, ATTENTION) * 8
    d_model: int = 3840
    n_head: int = 30
    n_kv_head: int = 30
    gdn_heads: int = 30
    gdn_key_dim: int = 96
    gdn_value_dim: int = 192
    gdn_conv: int = 4
    # kda_scan's chunk, chosen INSIDE a forward of three linear layers at
    # these widths (examples/probes/delta_scan_probe.py --layers 3, my chip
    # runs, PR 57; ms a forward at 1,024 | 4,096 positions): chunks of 64
    # 11.25 | 56.00, of 128 10.77 | 52.55 (of 256 63.81 at 4,096, an earlier
    # run); the form before PR 57 (a triangular solve a chunk of 32, --tree
    # a checkout of 0b5bcbc) 14.34 | 56.71.  One layer's scan ALONE pays
    # layout copies that a program's neighbours absorb and tells the chunks
    # apart no more (30 heads of 96 x 192, ms at 1,024 | 2,048 | 4,096,
    # before PR 57 and then with PR 57's first form of the inverse: chunks of
    # 32 1.31 | 2.37 | 5.62 and 0.97 | 2.12 | 5.85; of 64 3.19 | 4.68 | 8.52
    # and 0.85 | 2.14 | 5.64; of 128 3.59 | 7.15 | 15.30 and 0.73 | 2.21 |
    # 5.68; as committed 6.16 at 4,096 in chunks of 128).  ``M`` and ``B``
    # are ``d_k x (d_k + d_v)`` a chunk: at 32 they are 425 MB a layer at
    # 4,096 positions (the cell's prefill[4096] passes its 0.9 GB of
    # temporaries), at 128 106 MB.
    gdn_chunk: int = 128
    d_ff: int = 11008
    max_seq: int = 65536
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    attn_impl: str = "dense"
    remat: bool = True
    mesh: Any = None

    def __post_init__(self):
        bad = set(self.layer_types) - {GDN, ATTENTION}
        if bad:
            raise ValueError(f"layer_types holds {sorted(bad)}")

    @staticmethod
    def tiny(**overrides) -> "OlmoHybridConfig":
        """The shape at a test's size: two periods of three and one, 60
        wide; 6 GDN heads (no block of 16 divides them) of 12 keys and 24
        values (neither a power of two), chunks of 8; 6 attention heads of
        10; a SwiGLU of 96."""
        return OlmoHybridConfig(**{**dict(
            vocab_size=256, layer_types=(GDN, GDN, GDN, ATTENTION) * 2,
            d_model=60, n_head=6, n_kv_head=6, gdn_heads=6, gdn_key_dim=12,
            gdn_value_dim=24, gdn_chunk=8, d_ff=96, max_seq=128,
            dtype=jnp.float32, param_dtype=jnp.float32), **overrides})

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def conv_dim(self) -> int:
        """The one convolution's channels: q's, k's and v's."""
        return self.gdn_heads * (2 * self.gdn_key_dim + self.gdn_value_dim)

    def layers_of(self, kind: str) -> int:
        return sum(t == kind for t in self.layer_types)

    def mixer_params(self) -> int:
        """One GDN layer's mixer (``wq``, ``wk``, ``wv``, ``wg``, ``wo``,
        ``wa``, ``wb`` and the taps), in parameters."""
        d, h = self.d_model, self.gdn_heads
        return d * h * (2 * self.gdn_key_dim + 3 * self.gdn_value_dim) \
            + 2 * d * h + self.gdn_conv * self.conv_dim

    def attention_params(self) -> int:
        """One attention layer's four matrices, in parameters."""
        dh = self.d_model // self.n_head
        return 2 * self.d_model * dh * (self.n_head + self.n_kv_head)

    # What ``models/decoder.py`` reads besides the fields: the kinds, the
    # norm's placement, and the FFN (every layer dense).
    norm_output = True
    experts = None

    @property
    def mixers(self):
        return MIXERS

    @property
    def n_dense_layers(self) -> int:
        return self.n_layer


def _out_gate(z):
    """What the mixer's normed output is multiplied by."""
    return nn.silu(z)


class GDNMixer(nn.Module):
    cfg: OlmoHybridConfig

    @nn.compact
    def __call__(self, u, cache=None):
        """u [B, T, d] -> [B, T, d]; with ``cache`` ({"conv", "ssm",
        "layer", "slots", "positions"}: the WHOLE state pool and this
        mixer's layer in it) returns (out, (conv, ssm)) with each row's
        slot updated."""
        cfg = self.cfg
        b, t, _ = u.shape
        h, dk, dv = cfg.gdn_heads, cfg.gdn_key_dim, cfg.gdn_value_dim
        f32 = jnp.float32
        init = nn.initializers.normal(0.02)
        dense = functools.partial(nn.Dense, use_bias=False,
                                  dtype=cfg.dtype, kernel_init=init)
        with jax.named_scope("gdn.proj"):
            qkv = jnp.concatenate(
                [dense(h * width, name=name)(u) for name, width in
                 (("wq", dk), ("wk", dk), ("wv", dv))], axis=-1)
            z = dense(h * dv, name="wg")(u)
            a_logit = dense(h, name="wa")(u)
            b_logit = dense(h, name="wb")(u)
        conv_w = self.param("conv_w", init, (cfg.gdn_conv, cfg.conv_dim),
                            f32)
        a_log = self.param("A_log", nn.initializers.zeros, (h,), f32)
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (h,), f32)

        valid = fresh = window = ssm_pool = None
        if cache is not None:
            valid = cache["positions"] >= 0                    # [B, T]
            fresh = cache["positions"][:, 0] == 0              # [B]
            ssm_pool, layer, slots = (cache["ssm"], cache["layer"],
                                      cache["slots"])
            window = (cache["conv"], layer, slots, fresh, valid)
        with jax.named_scope("gdn.conv"):
            if window is not None and t == 1:
                qkv, conv_pool = step_conv(qkv, conv_w, window, nn.silu)
            else:
                qkv, conv_pool = slot_conv(qkv, conv_w, window, act=nn.silu)
        with jax.named_scope("gdn.gate"):
            q, k, v = (x.reshape(b, t, h, -1) for x in jnp.split(
                qkv, (h * dk, 2 * h * dk), axis=-1))
            q, k = _l2_normalised(q) * dk ** -0.5, _l2_normalised(k)
            g = -jnp.exp(a_log) * jax.nn.softplus(
                a_logit.astype(f32) + dt_bias)                 # [B,T,H]
            beta = 2.0 * jax.nn.sigmoid(b_logit.astype(f32))   # [B,T,H]
            if valid is not None:    # padding: the identity
                g = jnp.where(valid[..., None], g, 0.0)
                beta = jnp.where(valid[..., None], beta, 0.0)
            g = g[..., None]                                   # one a head
        if cache is not None and t == 1:
            with jax.named_scope("gdn.step"):
                o, ssm_pool = kda_step(
                    ssm_pool, layer, slots, fresh, q[:, 0], k[:, 0],
                    v[:, 0], jnp.exp(g[:, 0]), beta[:, 0])
                o = o[:, None]
        else:
            with jax.named_scope("gdn.scan"):
                s_in = None
                if cache is not None:
                    s_in = load_states(ssm_pool, layer, slots, fresh, h)
                # (a scalar decay's scores have no blocks inside a chunk:
                # ``sub`` is the rows of the inverse's diagonal blocks and
                # rounds a short forward up, to whole sublanes)
                o, s_out = kda_scan(q, k, v, g, beta, cfg.gdn_chunk, 8, s_in)
                if cache is not None:
                    ssm_pool = store_states(ssm_pool, layer, slots, s_out)
        with jax.named_scope("gdn.out_norm"):
            y = RMSNorm(cfg.rms_eps, f32, name="o_norm")(o) \
                * _out_gate(z.astype(f32).reshape(b, t, h, dv))
            y = y.reshape(b, t, h * dv).astype(cfg.dtype)
        with jax.named_scope("gdn.out_proj"):
            out = dense(cfg.d_model, name="wo")(y)
        return out if cache is None else (out, (conv_pool, ssm_pool))


class OlmoHybrid(Decoder):
    """``models/decoder.py Decoder`` over an OlmoHybridConfig: a step runs
    against BOTH caches (``kv_cache`` = {"k_pages", "v_pages", "page_table",
    "conv", "ssm", "slots"}, ``positions`` [B, T]; the module docstring has
    the shapes)."""


def _state_shapes(cfg):
    from ..ops.delta_rule import state_shape

    return {"conv_shape": (cfg.gdn_conv - 1, cfg.conv_dim),
            "ssm_shape": state_shape(cfg.gdn_heads, cfg.gdn_key_dim,
                                     cfg.gdn_value_dim)}


MIXERS = {
    GDN: Mixer(GDNMixer, "gdn", ("conv", "ssm"), _state_shapes),
    # no position encoding; an RMSNorm over the whole q and k
    ATTENTION: attention_kind(functools.partial(
        Attention, rope=False, qk_norm="width")),
}


# ------------------------------------------------------ init, loss, rules

def olmo_hybrid_init(cfg: OlmoHybridConfig, rng):
    """The weights from the seed, leaf by leaf (``models/layers.py
    init_by_leaf``): matrices, the embedding and the head normal(0, 0.02),
    norm scales 1, and the mixer's own, which stay float32 (they feed the
    decay): ``A_log = log(uniform(1, 16))`` and ``dt_bias`` the inverse
    softplus of a step log-uniform in [0.001, 0.1], both a head (a token's
    log-decay lies in about -1.6 .. -0.001, so a chunk of 64 can pass
    e^-100), the taps uniform in +-1/sqrt(taps)."""
    return init_by_leaf(OlmoHybrid, cfg, rng,
                        functools.partial(delta_rule_leaf, cfg.gdn_conv))


olmo_hybrid_loss_fn = functools.partial(next_token_loss, OlmoHybrid)


def olmo_hybrid_partition_rules():
    """``models/decoder.py decoder_rules`` after the mixer's own: the
    output gate column-parallel into the heads (as ``wq`` / ``wk`` / ``wv``
    are), the two per-head projections whole on ``tensor``, the mixer's
    small leaves whole."""
    return decoder_rules(
        (r"wg/kernel$", PS("fsdp", "tensor")),
        (r"(wa|wb)/kernel$", PS("fsdp", None)),
        (r"(conv_w|A_log|dt_bias)$", PS()))

