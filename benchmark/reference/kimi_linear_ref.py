"""Kimi-Linear (HF ``model_type`` kimi_linear; moonshotai/Kimi-Linear-48B-
A3B-Instruct) in plain float32 ``jax.numpy``: forward, training loss and
gradients.  No flax, no cache, no chunked scan, no latent-space attention,
no sort, no grouped matmul: the delta rule runs token by token as its
equations say, every latent head's keys and values are expanded, each
token's experts by the layer equations.

``RMSNorm(x) = x / sqrt(mean(x^2) + rms_norm_eps) * g``.  ``x = E[tokens]``.
Layer ``l`` (1-indexed as the source lists them): ``h = x + mixer_l(
RMSNorm(x))``; ``x = h + ffn_l(RMSNorm(h))``.  Output: ``RMSNorm(x) W_head``
(untied).  No bias anywhere.  ``mixer_l`` is KDA for ``l`` in
``linear_attn_config.kda_layers`` and latent attention for ``l`` in
``linear_attn_config.full_attn_layers``.

KDA on ``u`` [T, d] (``H = linear_attn_config.num_heads`` heads of ``D =
linear_attn_config.head_dim``; ``K = short_conv_kernel_size`` taps):

- ``q^ = u W_q``, ``k^ = u W_k``, ``v^ = u W_v`` [T, H D].  Each through its
  own depthwise causal convolution and SiLU: ``conv(z)_t = sum_{j<K} w_j *
  z_{t-K+1+j}``, zeros before position 0 (the program holds the three
  sets of taps side by side as ``conv_w`` [K, 3 H D]: q's, k's, v's).
- Per head ``q = q' / max(|q'|_2, 1e-6) * D ** -0.5``; ``k = k' / max(|k'|_2,
  1e-6)``.
- ``g = -exp(A_log[h]) * softplus((u W_fa) W_fb + dt_bias)`` [T, H, D]: the
  log of the decay of each KEY channel; ``a = exp(g)``.  ``beta = sigmoid(u
  W_b)`` [T, H].
- ``S_0 = 0`` [D (key), D (value)] a head.  ``S~ = diag(a_t) S_{t-1}``; ``S_t
  = S~ + beta_t k_t (v_t - S~^T k_t)^T``; ``o_t = S_t^T q_t``.
- ``y = RMSNorm_D(o_t) * sigmoid((u W_ga) W_gb)`` per head (ONE scale of D
  shared by the heads); ``y W_o``.

Latent attention with no positions (``mla_use_nope``; ``q_lora_rank`` null):
``u W_q`` -> [H, d_n + d_r] = ``q_n | q_r``; ``u W_kva`` [r_kv + d_r] = ``c |
k_r``; ``c_kv = RMSNorm(c)``; ``c_kv W_kvb`` -> [H, d_n + d_v] = ``k_n | v``;
``k_r`` is one vector shared by the heads; nothing is rotated; scores ``(q_n
. k_n + q_r . k_r) * (d_n + d_r) ** -0.5``; causal softmax; ``sum p v`` ->
``W_o``.

FFN: ``l <= first_k_dense_replace`` a SwiGLU of ``intermediate_size``; the
rest ``shared(x) + routed(x)``: shared a SwiGLU of ``moe_intermediate_size *
num_shared_experts``; ``s = sigmoid(x W_g)`` over ALL
``published.num_experts`` experts (the file's ``num_experts`` where there is
no ``published`` group); chosen: the ``num_experts_per_token`` largest of ``s
+ e_score_correction_bias`` (``num_expert_group`` = ``topk_group`` = 1: one
group, the plain choice; another value is refused); weights ``s_i /
(sum_chosen s + 1e-20)`` (``moe_renormalize``) times
``routed_scaling_factor``; ``routed`` sums over the chosen experts that are
HELD (``first_expert`` .. + ``num_experts``: one chip's share under expert
parallelism; what the absent ones would add is left out, as the program
leaves it out).

Departures, noted: (1) what the config does not give is in the
configuration file's ``assumed`` (the gates' rank, the draws of ``A_log``,
``dt_bias`` and the taps, no conv bias, the 1e-6 under the L2 norms) and is
data here: the reference reads the program's tree.  (2) The selection bias
is data (the leaf ``expert_bias``) and takes no gradient.  (3) ``forward`` is
eager, attention in blocks of ``ATTN_BLOCK`` query positions and expert by
expert over the rows that chose it (``kimi_k2_ref.py``); the recurrence is
a ``lax.scan`` over the positions, ONE token a step, jitted by its shapes;
``forward(by_layer=True)`` runs the same equations a layer a ``jit`` with
the experts' static-shape form (for the timed sizes on the chip);
``loss_and_grads`` traces whole under ``jit``.

Parameters come in the program's own tree (``{"params": {"embed", "lm_head",
"layer_<i>": {"mixer_norm", "kda": {"wq", "wk", "wv", "f_a", "f_b", "g_a",
"g_b", "wb", "conv_w", "A_log", "dt_bias", "o_norm", "wo"} or "attn": {"wq",
"wkv_a", "kv_norm", "wkv_b", "wo"}, "mlp_norm", the FFN as Kimi-K2's},
"norm_f"}}``) in whatever dtype the program holds them and are read as
float32: weights are data.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from .kimi_k2_ref import (F32, _experts_eager, _experts_masked,  # noqa: F401
                          _rms_norm, _swiglu)

ATTN_BLOCK = 256
L2_EPS = 1e-6


def k2_config(config: dict) -> dict:
    """The router's and the experts' keys under the names ``kimi_k2_ref``'s
    expert functions read (the two sources name the same things
    differently)."""
    if config.get("moe_router_activation_func", "sigmoid") != "sigmoid":
        raise ValueError(config["moe_router_activation_func"])
    return {"n_routed_experts": config["num_experts"],
            "first_routed_expert": config.get("first_expert", 0),
            "num_experts_per_tok": config["num_experts_per_token"],
            "norm_topk_prob": config.get("moe_renormalize", True),
            "scoring_func": "sigmoid",
            "n_group": config.get("num_expert_group", 1),
            "topk_group": config.get("topk_group", 1),
            "routed_scaling_factor": config["routed_scaling_factor"]}


def _dense(x, p):
    return x @ p["kernel"].astype(F32)


def _short_conv(z, taps):
    """z [B, T, C], taps [K, C]: ``sum_j taps[j] z_{t-K+1+j}``, then SiLU."""
    k, t = taps.shape[0], z.shape[1]
    past = jnp.pad(z, ((0, 0), (k - 1, 0), (0, 0)))
    return jax.nn.silu(sum(past[:, j:j + t] * taps[j] for j in range(k)))


@jax.jit
def delta_rule(q, k, v, a, beta):
    """The recurrence, one token a step.  q, k, v, a [B, T, H, D], beta [B,
    T, H] -> o [B, T, H, D]."""
    b, _, h, d = q.shape

    def step(s, x):     # multiplies and sums in float32: no matmul unit
        q_t, k_t, v_t, a_t, beta_t = x
        s = a_t[..., None] * s                              # diag(a) S
        err = v_t - jnp.sum(s * k_t[..., None], axis=-2)    # v - S~^T k
        s = s + (beta_t[..., None] * k_t)[..., None] * err[..., None, :]
        return s, jnp.sum(s * q_t[..., None], axis=-2)      # S^T q

    _, o = jax.lax.scan(step, jnp.zeros((b, h, d, d), F32), tuple(
        jnp.moveaxis(z, 1, 0) for z in (q, k, v, a, beta)))
    return jnp.moveaxis(o, 0, 1)


def _kda(u, p, config):
    lin = config["linear_attn_config"]
    h, d = lin["num_heads"], lin["head_dim"]
    b, t, _ = u.shape
    taps = p["conv_w"].astype(F32)
    q, k, v = (_short_conv(_dense(u, p[name]), taps[:, i * h * d:
                                                    (i + 1) * h * d])
               .reshape(b, t, h, d)
               for i, name in enumerate(("wq", "wk", "wv")))
    q, k = (x / jnp.maximum(jnp.sqrt(jnp.sum(x * x, -1, keepdims=True)),
                            L2_EPS) for x in (q, k))
    q = q * d ** -0.5
    f = _dense(_dense(u, p["f_a"]), p["f_b"]) + p["dt_bias"].astype(F32)
    g = -jnp.exp(p["A_log"].astype(F32))[:, None] \
        * jax.nn.softplus(f.reshape(b, t, h, d))
    beta = jax.nn.sigmoid(_dense(u, p["wb"]))
    o = delta_rule(q, k, v, jnp.exp(g), beta)
    z = _dense(_dense(u, p["g_a"]), p["g_b"]).reshape(b, t, h, d)
    y = _rms_norm(o, p["o_norm"], float(config["rms_norm_eps"])) \
        * jax.nn.sigmoid(z)
    return _dense(y.reshape(b, t, h * d), p["wo"])


def _mla(u, p, config, block):
    if not config.get("mla_use_nope") or config.get("q_lora_rank"):
        raise ValueError("the reference writes the published choice down: "
                         "no rotation, no query rank")
    b, t, _ = u.shape
    h, r_kv = config["num_attention_heads"], config["kv_lora_rank"]
    d_n, d_r, d_v = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                     config["v_head_dim"])
    q = _dense(u, p["wq"]).reshape(b, t, h, d_n + d_r)
    ckv = _dense(u, p["wkv_a"])
    c_kv = _rms_norm(ckv[..., :r_kv], p["kv_norm"],
                     float(config["rms_norm_eps"]))
    k_r = ckv[..., r_kv:]                                    # [B, T, d_r]
    kv = (c_kv @ p["wkv_b"].astype(F32)).reshape(b, t, h, d_n + d_v)
    k_n, v = kv[..., :d_n], kv[..., d_n:]
    scale = (d_n + d_r) ** -0.5
    outs = []
    for lo in range(0, t, block or t):
        hi = min(lo + (block or t), t)
        scores = (jnp.einsum("bqhd,bkhd->bhqk", q[:, lo:hi, :, :d_n],
                             k_n[:, :hi])
                  + jnp.einsum("bqhd,bkd->bhqk", q[:, lo:hi, :, d_n:],
                               k_r[:, :hi])) * scale
        causal = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        scores = jnp.where(causal, scores, -jnp.inf)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd",
                               jax.nn.softmax(scores, axis=-1), v[:, :hi]))
    att = jnp.concatenate(outs, axis=1).reshape(b, t, h * d_v)
    return _dense(att, p["wo"])


def _layer(config, kda: bool, dense: bool, layer, x, experts, block):
    """One block: ``x + mixer(norm(x))``, then ``+ ffn(norm(.))``."""
    eps = float(config["rms_norm_eps"])
    b, t, d = x.shape
    u = _rms_norm(x, layer["mixer_norm"], eps)
    x = x + (_kda(u, layer["kda"], config) if kda
             else _mla(u, layer["attn"], config, block))
    h = _rms_norm(x, layer["mlp_norm"], eps).reshape(b * t, d)
    if dense:
        y = _swiglu(h, *(layer[k]["kernel"]
                         for k in ("w_gate", "w_up", "w_down")))
    else:
        y = experts(h, layer["moe"], k2_config(config)) + _swiglu(
            h, *(layer[k]["kernel"] for k in
                 ("shared_gate", "shared_up", "shared_down")))
    return x + y.reshape(b, t, d)


@functools.lru_cache(maxsize=None)
def _compiled_layer(config_json: str, kda: bool, dense: bool):
    """``_layer`` under ``jit`` (the experts with static shapes), one
    program a kind of layer: the layers of a kind share it."""
    config = json.loads(config_json)
    return jax.jit(lambda layer, x: _layer(
        config, kda, dense, layer, x, _experts_masked, ATTN_BLOCK))


@jax.jit
def _head(x, scale, w, eps):
    return _rms_norm(x, scale, eps) @ w.astype(F32)


def _run(config, params, tokens, experts, block, last=0, lengths=None,
         by_layer=False):
    p = params["params"]
    eps = float(config["rms_norm_eps"])
    lin = config["linear_attn_config"]
    n = config["num_hidden_layers"]
    kda = set(lin["kda_layers"])
    if kda | set(lin["full_attn_layers"]) != set(range(1, n + 1)) \
            or kda & set(lin["full_attn_layers"]):
        raise ValueError("kda_layers and full_attn_layers must part the "
                         f"layers 1..{n} between them")
    x = p["embed"][tokens].astype(F32)
    key = json.dumps(config, sort_keys=True)
    for i in range(n):
        kind = (i + 1 in kda, i < config["first_k_dense_replace"])
        if by_layer:
            x = _compiled_layer(key, *kind)(p[f"layer_{i}"], x)
        else:
            x = _layer(config, *kind, p[f"layer_{i}"], x, experts, block)
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
    > 0: of the last ``last`` positions alone (the head over 4,000
    positions of 163,840 ids would be 2.6 GB), with ``lengths`` [B] those
    that end at each row's own length (the rows filled behind it to one
    T: what lies behind a position changes nothing before it).
    ``by_layer``: each layer under ``jit``, one program a kind of layer, the
    experts with static shapes (every held expert over every row, times its
    weight or 0): the same sums, and what the timed sizes need, where the
    eager form compiles each expert's matmuls anew for every count of rows
    that chose it (140-220 s a row of 2,300 positions on the chip, PR 41)."""
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
