"""Olmo-Hybrid (HF ``model_type`` olmo_hybrid; allenai/Olmo-Hybrid-7B) in
plain float32 ``jax.numpy``: forward, training loss and gradients.  No flax,
no cache, no kernel, no chunked scan: the delta rule runs token by token as
its equations say, attention over the whole score matrix (in blocks of query
positions, for memory alone).

``RMSNorm(x) = x / sqrt(mean(x^2) + rms_norm_eps) * g``.  ``x = E[tokens]``.
Layer ``l`` norms each sublayer's OUTPUT: ``h = x + RMSNorm(mixer_l(x))``;
``x = h + RMSNorm(mlp(h))``; ``mlp(h) = W_down(silu(W_gate h) * W_up h)``.
Output: ``RMSNorm(x) W_head`` (untied).  No bias anywhere.  ``mixer_l`` is
what ``layer_types[l]`` says.

``full_attention`` on ``u`` [T, d]: ``q = RMSNorm_d(u W_q)``, ``k =
RMSNorm_d(u W_k)`` (over the whole width, before the split), ``v = u W_v``;
``num_attention_heads`` heads of ``d / num_attention_heads``,
``num_key_value_heads`` K/V heads (a query head ``i`` reads K/V head ``i //
(H / H_kv)``); nothing is rotated; scores ``q . k * head_dim ** -0.5``;
causal softmax; ``sum p v`` -> ``W_o``.

``linear_attention`` (Gated DeltaNet) on ``u`` [T, d], ``H =
linear_num_value_heads`` heads of ``d_k = linear_key_head_dim`` and ``d_v =
linear_value_head_dim``, ``K = linear_conv_kernel_dim`` taps:

- ``q^ = u W_q``, ``k^ = u W_k`` [T, H d_k], ``v^ = u W_v`` [T, H d_v]; each
  channel through a causal convolution of its own and SiLU: ``conv(z)_t =
  sum_{j<K} w_j * z_{t-K+1+j}``, zeros before position 0 (the program holds
  the taps side by side as ``conv_w`` [K, 2 H d_k + H d_v]: q's, k's, v's).
- Per head ``q = q' / max(|q'|_2, 1e-6) * d_k ** -0.5``; ``k = k' / max(
  |k'|_2, 1e-6)``.
- ``g = -exp(A_log[h]) * softplus(u W_a + dt_bias[h])`` [T, H]: ONE number a
  head; ``a = exp(g)``.  ``beta = 2 sigmoid(u W_b)`` [T, H]
  (``linear_allow_neg_eigval``; without it the factor is 1).
- ``S_0 = 0`` [d_k, d_v] a head.  ``S~ = a_t S_{t-1}``; ``S_t = S~ + beta_t
  k_t (v_t - S~^T k_t)^T``; ``o_t = S_t^T q_t``.
- ``y = RMSNorm_{d_v}(o_t) * silu(u W_g)`` per head (ONE scale of d_v shared
  by the heads); ``y W_o``.

Departures, noted: (1) what the config does not give is in the
configuration file's ``assumed`` (the block's norm placement, the QK-norm,
no rotation, the gate's SiLU, the draws of ``A_log``, ``dt_bias`` and the
taps, the 1e-6 under the L2 norms) and is written down here as the
equations above; the drawn leaves are data: the reference reads the
program's tree.  (2) ``forward`` is eager, a layer's recurrence a
``lax.scan`` over the positions, ONE token a step, jitted by its shapes;
``forward(by_layer=True)`` runs the same equations a layer a ``jit`` (for
the timed sizes on the chip); ``loss_and_grads`` traces whole under ``jit``.

Parameters come in the program's own tree (``{"params": {"embed",
"lm_head", "layer_<i>": {"mixer_norm", "gdn": {"wq", "wk", "wv", "wg", "wa",
"wb", "conv_w", "A_log", "dt_bias", "o_norm", "wo"} or "attn": {"wq", "wk",
"wv", "q_norm", "k_norm", "wo"}, "mlp_norm", "w_gate", "w_up", "w_down"},
"norm_f"}}``) in whatever dtype the program holds them and are read as
float32: weights are data.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

F32 = jnp.float32
ATTN_BLOCK = 256
L2_EPS = 1e-6


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * p["scale"].astype(F32)


def _dense(x, p):
    return x @ p["kernel"].astype(F32)


def _short_conv(z, taps):
    """z [B, T, C], taps [K, C]: ``sum_j taps[j] z_{t-K+1+j}``, then SiLU."""
    k, t = taps.shape[0], z.shape[1]
    past = jnp.pad(z, ((0, 0), (k - 1, 0), (0, 0)))
    return jax.nn.silu(sum(past[:, j:j + t] * taps[j] for j in range(k)))


@jax.jit
def delta_rule(q, k, v, a, beta):
    """The recurrence, one token a step.  q, k [B, T, H, d_k], v [B, T, H,
    d_v], a, beta [B, T, H] -> o [B, T, H, d_v]."""
    b, _, h, dk = q.shape

    def step(s, x):     # multiplies and sums in float32: no matmul unit
        q_t, k_t, v_t, a_t, beta_t = x
        s = a_t[..., None, None] * s                        # a S
        err = v_t - jnp.sum(s * k_t[..., None], axis=-2)    # v - S~^T k
        s = s + (beta_t[..., None] * k_t)[..., None] * err[..., None, :]
        return s, jnp.sum(s * q_t[..., None], axis=-2)      # S^T q

    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, v.shape[-1]), F32), tuple(
        jnp.moveaxis(z, 1, 0) for z in (q, k, v, a, beta)))
    return jnp.moveaxis(o, 0, 1)


def _gated_delta_net(u, p, config):
    h, dk, dv = (config["linear_num_value_heads"],
                 config["linear_key_head_dim"],
                 config["linear_value_head_dim"])
    if config["linear_num_key_heads"] != h:
        raise ValueError("the reference writes the published choice down: "
                         "as many key heads as value heads")
    b, t, _ = u.shape
    taps = p["conv_w"].astype(F32)
    at = (0, h * dk, 2 * h * dk, 2 * h * dk + h * dv)
    q, k, v = (_short_conv(_dense(u, p[name]), taps[:, lo:hi])
               .reshape(b, t, h, -1)
               for name, lo, hi in zip(("wq", "wk", "wv"), at, at[1:]))
    q, k = (x / jnp.maximum(jnp.sqrt(jnp.sum(x * x, -1, keepdims=True)),
                            L2_EPS) for x in (q, k))
    q = q * dk ** -0.5
    g = -jnp.exp(p["A_log"].astype(F32)) * jax.nn.softplus(
        _dense(u, p["wa"]) + p["dt_bias"].astype(F32))
    beta = jax.nn.sigmoid(_dense(u, p["wb"]))
    if config["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    o = delta_rule(q, k, v, jnp.exp(g), beta)
    z = _dense(u, p["wg"]).reshape(b, t, h, dv)
    y = _rms_norm(o, p["o_norm"], float(config["rms_norm_eps"])) \
        * jax.nn.silu(z)
    return _dense(y.reshape(b, t, h * dv), p["wo"])


def _attention(u, p, config, block):
    b, t, d = u.shape
    h, h_kv = config["num_attention_heads"], config["num_key_value_heads"]
    dh = d // h
    eps = float(config["rms_norm_eps"])
    q = _rms_norm(_dense(u, p["wq"]), p["q_norm"], eps).reshape(b, t, h, dh)
    k = _rms_norm(_dense(u, p["wk"]), p["k_norm"], eps).reshape(
        b, t, h_kv, dh)
    v = _dense(u, p["wv"]).reshape(b, t, h_kv, dh)
    k, v = (jnp.repeat(x, h // h_kv, axis=2) for x in (k, v))
    outs = []
    for lo in range(0, t, block or t):
        hi = min(lo + (block or t), t)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, lo:hi], k[:, :hi]) \
            * dh ** -0.5
        causal = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        scores = jnp.where(causal, scores, -jnp.inf)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd",
                               jax.nn.softmax(scores, axis=-1), v[:, :hi]))
    return _dense(jnp.concatenate(outs, axis=1).reshape(b, t, d), p["wo"])


def _layer(config, kind: str, layer, x, block):
    """One block: ``x + norm(mixer(x))``, then ``+ norm(mlp(.))``."""
    eps = float(config["rms_norm_eps"])
    if kind == "linear_attention":
        m = _gated_delta_net(x, layer["gdn"], config)
    elif kind == "full_attention":
        m = _attention(x, layer["attn"], config, block)
    else:
        raise ValueError(kind)
    x = x + _rms_norm(m, layer["mixer_norm"], eps)
    y = _dense(jax.nn.silu(_dense(x, layer["w_gate"]))
               * _dense(x, layer["w_up"]), layer["w_down"])
    return x + _rms_norm(y, layer["mlp_norm"], eps)


@functools.lru_cache(maxsize=None)
def _compiled_layer(config_json: str, kind: str):
    """``_layer`` under ``jit``, one program a kind of layer."""
    config = json.loads(config_json)
    return jax.jit(lambda layer, x: _layer(config, kind, layer, x,
                                           ATTN_BLOCK))


@jax.jit
def _head(x, scale, w, eps):
    return _rms_norm(x, scale, eps) @ w.astype(F32)


def _run(config, params, tokens, block, last=0, lengths=None,
         by_layer=False):
    p = params["params"]
    eps = float(config["rms_norm_eps"])
    kinds = config["layer_types"]
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError("layer_types must have num_hidden_layers entries")
    if config["hidden_act"] != "silu" or config["attention_bias"] \
            or config["tie_word_embeddings"] \
            or config["rope_parameters"]["rope_theta"] is not None:
        raise ValueError("the reference writes the published choices down: "
                         "SiLU, no bias, an untied head, no rotation")
    x = p["embed"][tokens].astype(F32)
    key = json.dumps(config, sort_keys=True)
    for i, kind in enumerate(kinds):
        if by_layer:
            x = _compiled_layer(key, kind)(p[f"layer_{i}"], x)
        else:
            x = _layer(config, kind, p[f"layer_{i}"], x, block)
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
    > 0: of the last ``last`` positions alone, with ``lengths`` [B] those
    that end at each row's own length (the rows filled behind it to one T:
    what lies behind a position changes nothing before it).  ``by_layer``:
    each layer under ``jit``, one program a kind of layer."""
    with jax.default_matmul_precision("highest"):
        return _run(config, params, tokens, ATTN_BLOCK, last, lengths,
                    by_layer)


def loss(config: dict, params, tokens):
    """Mean next-token cross entropy over tokens [B, T+1]."""
    with jax.default_matmul_precision("highest"):
        logits = _run(config, params, tokens[:, :-1], 0)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None],
                                             axis=-1))


def loss_and_grads(config: dict, params, tokens):
    return jax.value_and_grad(lambda q: loss(config, q, tokens))(params)
