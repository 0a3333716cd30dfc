"""Xing4.0 (HF ``model_type`` xing4_0; XingChen-AGI/Xing4.0-29B-A4B) in
plain float32 ``jax.numpy``: forward, training loss and gradients.  No
flax, no cache, no kernels, no batching tricks.  The sublayers are the
DeepSeek-V3 block's, the same mathematics as ``kimi_k2_ref.py`` (expanded
multi-head latent attention with YaRN's frequencies; a dense SwiGLU in the
first ``first_k_dense_replace`` layers, then ``shared(x) + routed(x)`` with
sigmoid scores, the ``noaux_tc`` selection bias over ONE group,
``norm_topk_prob`` and ``routed_scaling_factor``: its docstring has the
equations, and they are taken from it as functions).  What this file
writes down is what joins them: the RESIDUAL PATH, manifold-constrained
hyper-connections (mHC, arXiv:2512.24880, over hyper-connections,
arXiv:2409.19606).

``n = hc_mult``.  A token's residual state is ``X`` in ``R^{n x d}``.
Entry: ``X_i = E[token]`` for every stream ``i``.  For each layer's two
sublayers ``F`` (attention with the norm ``attn_norm``, the FFN with
``mlp_norm``), each with maps of its own (``attn_hc`` | ``mlp_hc``: ``phi``
[n d, 2 n + n^2], ``norm.scale`` [n d], ``map_bias`` [2 n + n^2] = ``b_pre |
b_post | b_res``, ``map_gate`` [3] = ``a_pre, a_post, a_res``):

1. ``r = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps) * scale`` over the
   flattened ``n d`` numbers, stream after stream.
2. ``[p | q | s] = r Phi``.  ``H_pre = sigmoid(a_pre p + b_pre)`` [n];
   ``H_post = 2 sigmoid(a_post q + b_post)`` [n]; ``S = clamp(a_res mat(s) +
   b_res, mhc_h_res_clamp_min, mhc_h_res_clamp_max)`` [n, n], ``mat`` row by
   row; ``M = exp(S)``; ``hc_sinkhorn_iters`` times: ``M <- M / (column sums
   + hc_eps)``, then ``M <- M / (row sums + hc_eps)``; ``H_res = M``.
3. ``u = sum_i H_pre[i] X_i``;  ``y = F(RMSNorm(u))``.
4. ``X'_j = sum_i H_res[j, i] X_i + H_post[j] y``.

Exit: ``x = sum_i X_i``; logits ``RMSNorm(x) W_head`` (untied).  No bias
but the maps'.  The multi-token prediction module
(``num_nextn_predict_layers``) is not written: a config that asks for it is
refused.

Departures, noted: those of ``kimi_k2_ref.py`` (the rotary pairing, the
frozen selection bias, attention in blocks of query positions and the
experts by the rows that chose them).  The placements the source's config
does not give (entry, exit, columns before rows, where ``hc_eps`` and the
clamp enter, ``mat``'s order) are ``assumed`` in
benchmark/configs/xing4.0-29b-a4b.json.  ``forward(by_layer=True)`` runs
the same equations a layer a ``jit`` with the experts' static-shape form
(for the timed sizes on the chip); ``last`` > 0 gives the logits of the
last positions alone, of each row's own ``lengths`` where given.

Parameters come in the program's own tree (Kimi-K2's, with ``attn_hc`` and
``mlp_hc`` in every layer) in whatever dtype the program holds them and are
read as float32: weights are data.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from .kimi_k2_ref import (ATTN_BLOCK, F32, _attention,  # noqa: F401
                          _experts_eager, _experts_masked, _rms_norm,
                          _swiglu)


def sinkhorn(m, iters: int, eps: float):
    """m [..., n, n] positive -> after ``iters`` rounds of columns, then
    rows, each divided by its sum + ``eps``."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
    return m


def maps(x, hc, config):
    """Steps 1-2: X [B, T, n, d] -> (H_pre [B, T, n], H_post [B, T, n],
    H_res [B, T, n, n])."""
    b, t, n, d = x.shape
    r = _rms_norm(x.reshape(b, t, n * d), hc["norm"],
                  float(config["rms_norm_eps"]))
    z = r @ hc["phi"].astype(F32)
    a_pre, a_post, a_res = hc["map_gate"].astype(F32)
    bias = hc["map_bias"].astype(F32)
    h_pre = jax.nn.sigmoid(a_pre * z[..., :n] + bias[:n])
    h_post = 2.0 * jax.nn.sigmoid(a_post * z[..., n:2 * n] + bias[n:2 * n])
    s = (a_res * z[..., 2 * n:] + bias[2 * n:]).reshape(b, t, n, n)
    s = jnp.clip(s, float(config["mhc_h_res_clamp_min"]),
                 float(config["mhc_h_res_clamp_max"]))
    return h_pre, h_post, sinkhorn(jnp.exp(s), config["hc_sinkhorn_iters"],
                                   float(config["hc_eps"]))


def _sublayer(x, hc, config, f):
    """Steps 1-4 around ``f`` ([B, T, d] -> [B, T, d], its norm inside)."""
    h_pre, h_post, h_res = maps(x, hc, config)
    u = jnp.einsum("bti,btid->btd", h_pre, x)
    return jnp.einsum("btji,btid->btjd", h_res, x) \
        + h_post[..., None] * f(u)[:, :, None]


def _layer(config, dense: bool, layer, x, experts, block):
    eps = float(config["rms_norm_eps"])

    def attn(u):
        return _attention(_rms_norm(u, layer["attn_norm"], eps),
                          layer["attn"], config, block)

    def ffn(u):
        b, t, d = u.shape
        h = _rms_norm(u, layer["mlp_norm"], eps).reshape(b * t, d)
        if dense:
            y = _swiglu(h, *(layer[k]["kernel"]
                             for k in ("w_gate", "w_up", "w_down")))
        else:
            y = experts(h, layer["moe"], config) + _swiglu(
                h, *(layer[k]["kernel"] for k in
                     ("shared_gate", "shared_up", "shared_down")))
        return y.reshape(b, t, d)

    x = _sublayer(x, layer["attn_hc"], config, attn)
    return _sublayer(x, layer["mlp_hc"], config, ffn)


@functools.lru_cache(maxsize=None)
def _compiled_layer(config_json: str, dense: bool):
    """``_layer`` under ``jit`` (the experts with static shapes), one
    program a kind of layer: the layers of a kind share it."""
    config = json.loads(config_json)
    return jax.jit(lambda layer, x: _layer(
        config, dense, layer, x, _experts_masked, ATTN_BLOCK))


@jax.jit
def _head(x, scale, w, eps):
    return _rms_norm(x, scale, eps) @ w.astype(F32)


def _run(config, params, tokens, experts, block, last=0, lengths=None,
         by_layer=False):
    if config.get("num_nextn_predict_layers", 0):
        raise ValueError("the multi-token prediction module is not "
                         "written down: num_nextn_predict_layers must be 0")
    p = params["params"]
    eps = float(config["rms_norm_eps"])
    e = p["embed"][tokens].astype(F32)
    x = jnp.stack([e] * config["hc_mult"], axis=2)      # X_i = E[token]
    key = json.dumps(config, sort_keys=True)
    for i in range(config["num_hidden_layers"]):
        dense = i < config["first_k_dense_replace"]
        if by_layer:
            x = _compiled_layer(key, dense)(p[f"layer_{i}"], x)
        else:
            x = _layer(config, dense, p[f"layer_{i}"], x, experts, block)
    x = jnp.sum(x, axis=2)
    if lengths is not None:     # the last positions of each row's OWN length
        at = jnp.asarray(lengths)[:, None] - last + jnp.arange(last)
        x = jnp.take_along_axis(x, at[..., None], axis=1)
    else:
        x = x[:, -last:]
    if by_layer:
        return _head(x, p["norm_f"], p["lm_head"], eps)
    return _rms_norm(x, p["norm_f"], eps) @ p["lm_head"].astype(F32)


def forward(config: dict, params, tokens, last: int = 0, lengths=None,
            by_layer: bool = False):
    """tokens [B, T] int -> logits [B, T, vocab] float32 (eager); ``last``
    > 0: of the last ``last`` positions alone (the head over 3,600
    positions of 131,072 ids would be 1.9 GB), with ``lengths`` [B] those
    that end at each row's own length (the rows filled behind it to one T:
    what lies behind a position changes nothing before it, and one T is
    one compile); ``by_layer``: each layer under ``jit``, every held
    expert over every row times its weight or 0 (the same sums)."""
    with jax.default_matmul_precision("highest"):
        return _run(config, params, tokens, _experts_eager, ATTN_BLOCK,
                    last, lengths, by_layer)


def loss(config: dict, params, tokens):
    """Mean next-token cross entropy over tokens [B, T+1]."""
    with jax.default_matmul_precision("highest"):
        logits = _run(config, params, tokens[:, :-1], _experts_masked, 0)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None],
                                             axis=-1))


def loss_and_grads(config: dict, params, tokens):
    return jax.value_and_grad(lambda q: loss(config, q, tokens))(params)
