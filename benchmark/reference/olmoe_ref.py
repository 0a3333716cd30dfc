"""OLMoE (Muennighoff et al. 2024, "OLMoE: Open Mixture-of-Experts
Language Models"; HF ``model_type`` olmoe) in plain float32 ``jax.numpy``:
forward, training loss and gradients.  No flax, no cache, no sort, no
grouped matmul: per token, its k experts, by the layer equations.

Per layer: ``h = RMSNorm_in(x)``; ``q, k, v = h Wq, h Wk, h Wv`` (no
bias); ``q = RMSNorm_q(q)``, ``k = RMSNorm_k(k)`` over the full-width
vectors, each with its own scale; split into heads; RoPE (rotate-half);
causal softmax attention scaled by 1/sqrt(head size); ``x = x + att Wo``.
Then ``h = RMSNorm_post(x)``; router logits ``r = h Wg``; ``p =
softmax(r)`` over all experts; the k largest ``p`` (ties to the lower
index), left as they are unless ``norm_topk_prob``; ``y = sum_k p_k
W_down[e_k](silu(W_gate[e_k] h) * (W_up[e_k] h))``; ``x = x + y``.  Final
RMSNorm, untied ``lm_head``.  Loss: mean next-token cross entropy +
``router_aux_loss_coef`` x the load-balancing loss (``num_experts x sum_e
f_e P_e``; ``f_e`` the share of the ``S x k`` assignments that went to
expert ``e``, ``P_e`` the mean router probability) + ``router_z_loss_coef``
x the router z-loss (mean squared log-sum-exp of ``r``), both summed over
the layers.

Departures from the HF implementation (``modeling_olmoe.py``, written
down from memory: there is no network here), noted: (1) HF concatenates
the layers' router logits, takes ONE ``f`` (per top-k slot) and ``P`` over
layers x tokens, and returns ``num_experts x sum_slot sum_e f_slot,e P_e``:
k times this file's per-layer value, averaged over the layers where this
file sums them.  The issue's equations are the ones computed here; the
coefficient is ``assumed`` either way.  (2) HF's modelling code has no z-loss; the paper's training
had (coefficient 0.001), and it is here.  (3) ``clip_qkv`` is null in the
source and absent here.

``forward`` is eager: expert by expert over the rows that chose it (filled
up with zero rows of zero weight to a multiple of 16, for the compiler's
sake: ``_experts_eager``).
``loss_and_grads`` must trace under ``jit`` (check.py), where shapes are
static: there every expert runs on every row and a 0/1 mask keeps the
chosen ones (the same sums; 8 times the arithmetic at top-8 of 64).

Parameters come in the program's own tree (``{"params": {"embed",
"layer_<i>": {"attn_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wo",
"mlp_norm", "moe": {"router", "w_gate", "w_up", "w_down"}}, "norm_f",
"lm_head"}}``) in whatever dtype the program holds them (bfloat16 for the
published model) and are read as float32: weights are data.
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


def _attention(h, layer, config):
    b, t, d = h.shape
    n_head = config["num_attention_heads"]
    hd = d // n_head
    eps = float(config["rms_norm_eps"])
    q = _rms_norm(h @ layer["wq"]["kernel"].astype(F32), layer["q_norm"],
                  eps)
    k = _rms_norm(h @ layer["wk"]["kernel"].astype(F32), layer["k_norm"],
                  eps)
    v = h @ layer["wv"]["kernel"].astype(F32)

    def heads(z):
        return z.reshape(b, t, n_head, hd).transpose(0, 2, 1, 3)

    theta = float(config["rope_theta"])
    q, k, v = _rope(heads(q), theta), _rope(heads(k), theta), heads(v)
    scores = q @ k.transpose(0, 1, 3, 2) / math.sqrt(hd)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    att = jax.nn.softmax(scores, axis=-1) @ v
    return att.transpose(0, 2, 1, 3).reshape(b, t, d) \
        @ layer["wo"]["kernel"].astype(F32)


def _route(h, moe, config):
    """h [S, d] -> (router logits [S, E], probs [S, E], weights [S, k],
    experts [S, k])."""
    r = h @ moe["router"].astype(F32)
    p = jax.nn.softmax(r, axis=-1)
    w, e = jax.lax.top_k(p, config["num_experts_per_tok"])
    if config.get("norm_topk_prob", False):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return r, p, w, e


def _expert(h, moe, e):
    gate = h @ moe["w_gate"][e].astype(F32)
    up = h @ moe["w_up"][e].astype(F32)
    return (jax.nn.silu(gate) * up) @ moe["w_down"][e].astype(F32)


def _experts_eager(h, moe, config, block: int = 16):
    """Expert by expert over the rows that chose it (concrete values).
    An expert's rows are filled up to a multiple of ``block`` with a row
    of zeros that carries the weight zero (it adds nothing anywhere):
    eager ``jax.numpy`` compiles every operation once per SHAPE, and 64
    experts x 8 layers with a few dozen different row counts spent
    minutes compiling (138 s for one sequence of 255 positions, against
    under 1 s a layer once compiled; my runs, PR 26)."""
    _, _, w, chosen = _route(h, moe, config)
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
    """The same sums with static shapes; also the layer's router losses."""
    r, p, w, chosen = _route(h, moe, config)
    n = config["num_experts"]
    # gate[s, e] = the weight of expert e for row s, 0 where not chosen
    gate = jnp.sum(jax.nn.one_hot(chosen, n, dtype=F32) * w[..., None],
                   axis=1)
    y = jnp.zeros_like(h)
    for e in range(n):
        y = y + gate[:, e:e + 1] * _expert(h, moe, e)
    f = jnp.mean(jax.nn.one_hot(chosen, n, dtype=F32), axis=(0, 1))
    balance = n * jnp.sum(f * jnp.mean(p, axis=0))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(r, axis=-1)))
    return y, balance, z


def _run(config, params, tokens, experts):
    p = params["params"]
    eps = float(config["rms_norm_eps"])
    x = p["embed"].astype(F32)[tokens]
    b, t, d = x.shape
    extras = []
    for i in range(config["num_hidden_layers"]):
        layer = p[f"layer_{i}"]
        x = x + _attention(_rms_norm(x, layer["attn_norm"], eps), layer,
                           config)
        h = _rms_norm(x, layer["mlp_norm"], eps).reshape(b * t, d)
        y, *extra = experts(h, layer["moe"], config)
        extras.append(extra)
        x = x + y.reshape(b, t, d)
    x = _rms_norm(x, p["norm_f"], eps)
    return x @ p["lm_head"].astype(F32), extras


def forward(config: dict, params, tokens):
    """tokens [B, T] int -> logits [B, T, vocab] float32 (eager)."""
    with jax.default_matmul_precision("highest"):
        return _run(config, params, tokens,
                    lambda h, m, c: (_experts_eager(h, m, c),))[0]


def loss(config: dict, params, tokens):
    """Cross entropy + the two router losses over tokens [B, T+1]."""
    with jax.default_matmul_precision("highest"):
        logits, extras = _run(config, params, tokens[:, :-1],
                              _experts_masked)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ce = -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None],
                                           axis=-1))
        return ce \
            + config["router_aux_loss_coef"] * sum(b for b, _ in extras) \
            + config["router_z_loss_coef"] * sum(z for _, z in extras)


def loss_and_grads(config: dict, params, tokens):
    return jax.value_and_grad(lambda q: loss(config, q, tokens))(params)
