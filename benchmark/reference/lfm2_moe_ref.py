"""LFM2-MoE (HF ``model_type`` lfm2_moe; LiquidAI/LFM2-24B-A2B) in plain
float32 ``jax.numpy``: forward, training loss and gradients.  No flax, no
cache, no window kept between steps, no sort, no grouped matmul: the conv
over the whole sequence, each token's experts by the layer equations.

``RMSNorm(x) = x / sqrt(mean(x^2) + norm_eps) * g``.  ``x = E[tokens]``.
Layer ``l``: ``h = x + mixer_l(RMSNorm_op(x))``; ``x = h + ffn_l(RMSNorm_ffn(
h))``.  Output: ``RMSNorm(x) E^T`` (``embedding_norm``, then the head TIED to
the embedding).  No bias anywhere (``conv_bias`` false).

- Short-conv mixer (``layer_types[l] == "conv"``), ``u`` [T, d]: ``[B | C |
  X] = u W_in`` (each ``d`` wide, in this order); ``z = B * X``; ``c_t =
  sum_{j=0..2} w[j] * z_{t-2+j}`` with ``z`` zero before position 0 (a
  depthwise causal conv of ``conv_L_cache`` = 3 taps); ``out = (C * c)
  W_out``.  No activation function.
- Attention (``"full_attention"``): ``q = u W_q`` as ``num_attention_heads``
  heads, ``k``, ``v`` as ``num_key_value_heads``; RMSNorm over each head's
  own dimensions (``q_layernorm``, ``k_layernorm``: one scale [head size]
  each, shared by the heads), THEN RoPE (``rope_theta``, rotate-half, all
  the head's dimensions); causal softmax at ``1/sqrt(head size)``, each K/V
  head serving its group of query heads; ``W_out``.
- Dense FFN (``l < num_dense_layers``): ``(silu(x W1) * (x W3)) W2`` at
  ``intermediate_size``.
- Sparse FFN (the rest): ``r = x W_g`` (``num_experts`` logits); ``s =
  sigmoid(r)``; chosen: the ``num_experts_per_tok`` largest of ``s +
  expert_bias`` (ties to the lower index); weights ``w_i = s_i / (sum_chosen
  s + 1e-6)`` (``norm_topk_prob``; the bias is NOT in the weight) times
  ``routed_scaling_factor``; ``out = sum_i w_i (silu(x W1_i) * (x W3_i))
  W2_i`` at ``moe_intermediate_size``.  No shared expert.

Departures from HF's ``modeling_lfm2_moe.py`` (written down from memory:
there is no network here), noted: (1) HF keeps the conv's weight as
[d, 1, taps] and a cache of ``conv_L_cache`` columns of which the oldest is
never read again; here the taps are [taps, d] (the program's tree) and
nothing is kept.  (2) the ``1e-6`` in the renormalisation and the TIED head
are ``Lfm2MoeConfig``'s defaults as remembered; the catalog's row has
neither key: both are ``assumed`` in the configuration file.  (3) HF moves
``expert_bias`` by the experts' load outside autograd; here it is data and
takes no gradient.  (4) ``forward`` is eager, expert by expert over the
rows that chose it, each expert's rows filled up to a multiple of 16 with
zero rows of zero weight (so that eager compiles a handful of shapes:
``olmoe_ref.py``); ``loss_and_grads`` must trace under ``jit``, so there
every expert runs on every row under a 0/1 mask (the same sums).

At the published widths ten layers fit the host because nothing is held
in float32 but what is being used: an expert's three matrices are read as
float32 one expert at a time (37.7 MB), a dense layer's and a mixer's one
layer at a time, and the embedding once (537 MB; the logits of a sequence
of 1024 positions are 268 MB).

Parameters come in the program's own tree (``{"params": {"embed",
"layer_<i>": {"mixer_norm", "conv": {"in_proj", "conv_w", "out_proj"} or
"attn": {"wq", "wk", "wv", "q_norm", "k_norm", "wo"}, "mlp_norm", "w_gate",
"w_up", "w_down" (a dense layer) or "moe": {"router", "expert_bias",
"w_gate", "w_up", "w_down"}}, "norm_f"}}``) in whatever dtype the program
holds them and are read as float32: weights are data.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * p["scale"].astype(F32)


def _rope(x, theta):
    """x [B, H, T, D]: rotate-half rotary embedding at positions 0..T-1."""
    t, d = x.shape[2], x.shape[3]
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    angles = jnp.arange(t, dtype=F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1)


def _attention(u, p, config):
    b, t, d = u.shape
    n_q, n_kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = d // n_q
    eps = float(config["norm_eps"])
    theta = float(config["rope_parameters"]["rope_theta"])

    def heads(z, n):
        return z.reshape(b, t, n, hd).transpose(0, 2, 1, 3)

    q = heads(u @ p["wq"]["kernel"].astype(F32), n_q)
    k = heads(u @ p["wk"]["kernel"].astype(F32), n_kv)
    v = heads(u @ p["wv"]["kernel"].astype(F32), n_kv)
    q = _rope(_rms_norm(q, p["q_norm"], eps), theta)
    k = _rope(_rms_norm(k, p["k_norm"], eps), theta)
    k, v = (jnp.repeat(z, n_q // n_kv, axis=1) for z in (k, v))
    scores = q @ k.transpose(0, 1, 3, 2) / math.sqrt(hd)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    att = jax.nn.softmax(scores, axis=-1) @ v
    return att.transpose(0, 2, 1, 3).reshape(b, t, d) \
        @ p["wo"]["kernel"].astype(F32)


def _short_conv(u, p, config):
    b, t, d = u.shape
    taps = config["conv_L_cache"]
    gate_b, gate_c, x = jnp.split(u @ p["in_proj"]["kernel"].astype(F32), 3,
                                  axis=-1)
    z = jnp.concatenate([jnp.zeros((b, taps - 1, d), F32), gate_b * x],
                        axis=1)
    w = p["conv_w"].astype(F32)                        # [taps, d]
    c = sum(z[:, j:j + t] * w[j] for j in range(taps))
    return (gate_c * c) @ p["out_proj"]["kernel"].astype(F32)


def _dense(h, layer):
    gate = h @ layer["w_gate"]["kernel"].astype(F32)
    up = h @ layer["w_up"]["kernel"].astype(F32)
    return (jax.nn.silu(gate) * up) @ layer["w_down"]["kernel"].astype(F32)


def _route(h, moe, config):
    """h [S, d] -> (weights [S, k], experts [S, k])."""
    s = jax.nn.sigmoid(h @ moe["router"].astype(F32))
    _, chosen = jax.lax.top_k(s + moe["expert_bias"].astype(F32),
                              config["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if config.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True)
                 + float(config.get("route_norm_eps", 1e-6)))
    return w * float(config.get("routed_scaling_factor", 1)), chosen


def _expert(h, moe, e):
    gate = h @ moe["w_gate"][e].astype(F32)
    up = h @ moe["w_up"][e].astype(F32)
    return (jax.nn.silu(gate) * up) @ moe["w_down"][e].astype(F32)


def _experts_eager(h, moe, config, block: int = 16):
    """Expert by expert over the rows that chose it (concrete values), its
    rows filled to a multiple of ``block`` with a zero row of weight zero
    (benchmark/reference/olmoe_ref.py has the why)."""
    w, chosen = _route(h, moe, config)
    w, chosen = np.asarray(w), np.asarray(chosen)
    zero_row = h.shape[0]
    hz = jnp.concatenate([h, jnp.zeros_like(h[:1])])
    y = jnp.zeros_like(hz)
    for e in range(config["num_experts"]):
        rows, slot = np.nonzero(chosen == e)
        if rows.size:
            fill = -rows.size % block
            at = np.concatenate([rows, np.full(fill, zero_row)])
            weight = np.concatenate([w[rows, slot], np.zeros(fill, w.dtype)])
            y = y.at[at].add(weight[:, None] * _expert(hz[at], moe, e))
    return y[:zero_row]


def _experts_masked(h, moe, config):
    """The same sums with static shapes (traces under jit)."""
    w, chosen = _route(h, moe, config)
    n = config["num_experts"]
    gate = jnp.sum(jax.nn.one_hot(chosen, n, dtype=F32) * w[..., None],
                   axis=1)
    y = jnp.zeros_like(h)
    for e in range(n):
        y = y + gate[:, e:e + 1] * _expert(h, moe, e)
    return y


def _run(config, params, tokens, experts):
    p = params["params"]
    eps = float(config["norm_eps"])
    emb = p["embed"].astype(F32)
    x = emb[tokens]
    b, t, d = x.shape
    for i, kind in enumerate(
            config["layer_types"][:config["num_hidden_layers"]]):
        layer = p[f"layer_{i}"]
        u = _rms_norm(x, layer["mixer_norm"], eps)
        x = x + (_attention(u, layer["attn"], config)
                 if kind == "full_attention"
                 else _short_conv(u, layer["conv"], config))
        h = _rms_norm(x, layer["mlp_norm"], eps).reshape(b * t, d)
        y = _dense(h, layer) if i < config["num_dense_layers"] \
            else experts(h, layer["moe"], config)
        x = x + y.reshape(b, t, d)
    return _rms_norm(x, p["norm_f"], eps) @ emb.T


def forward(config: dict, params, tokens):
    """tokens [B, T] int -> logits [B, T, vocab] float32 (eager)."""
    with jax.default_matmul_precision("highest"):
        return _run(config, params, tokens, _experts_eager)


def loss(config: dict, params, tokens):
    """Mean next-token cross entropy over tokens [B, T+1]."""
    with jax.default_matmul_precision("highest"):
        logits = _run(config, params, tokens[:, :-1], _experts_masked)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None],
                                             axis=-1))


def loss_and_grads(config: dict, params, tokens):
    return jax.value_and_grad(lambda q: loss(config, q, tokens))(params)
