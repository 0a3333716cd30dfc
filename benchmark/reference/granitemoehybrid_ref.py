"""Granite 4.0-H (HF ``model_type`` granitemoehybrid; the Mamba-2 mixer of
Dao & Gu 2024, "Transformers are SSMs") in plain float32 ``jax.numpy``:
forward, training loss and gradients.  No flax, no cache, no chunks, no
sort, no grouped matmul: the state-space recurrence token by token
(``lax.scan`` over the positions), each token's experts by the layer
equations.

``x = embedding_multiplier x E[tokens]``.  Layer ``i``: ``h = RMSNorm(x)``;
``m = Mamba2(h)`` or ``Attn(h)`` by ``layer_types[i]``; ``x = x +
residual_multiplier x m``; ``h = RMSNorm(x)``; ``x = x + residual_multiplier
x (MoE(h) + Shared(h))``.  Output: ``RMSNorm(x) E^T / logits_scaling``.

- Attn: ``q, k, v = h Wq, h Wk, h Wv`` (no bias; grouped K/V heads), no
  position encoding of any kind, causal softmax of ``attention_multiplier x
  q k^T``, ``Wo``.
- Mamba2: ``[z | xBC | dt] = h W_in``; ``xBC = silu(conv(xBC) + b)`` with
  ``conv(u)_t = sum_i w_i u_{t-3+i}`` (depthwise, causal, 4 taps); ``xBC ->
  x`` (heads x head size), ``B``, ``C`` (one group); ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)``; per head ``S_t = exp(dt_t A) S_{t-1} +
  dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``; ``y = RMSNorm(y x silu(z))``
  over the whole inner width; ``y W_out``.
- MoE: ``r = h Wr`` over ALL ``published.num_local_experts`` experts; the
  ``num_experts_per_tok`` largest; softmax over those (= softmax over all,
  top-k, renormalised: ``ops/moe.py route`` with ``norm_topk_prob``);
  expert ``e``: ``W_down[e](silu(W_gate[e] h) x (W_up[e] h))``.  Only the
  experts HELD (``first_local_expert`` .. + ``num_local_experts``) are
  summed: what the absent ones would add is left out, as in the program,
  and the partial result goes on to the next layer.  Shared: the same
  gated form, every token, weight 1.

Departures from HF's ``modeling_granitemoehybrid.py`` (written down from
memory: there is no network here), noted: (1) HF holds each expert's gate
and up matrices, and the shared MLP's, as ONE ``input_linear`` that is
split in two after the product; here they are two matrices (the same
equations; with random weights no reordering is needed).  (2) HF's torch
path computes the mixer in chunks of ``mamba_chunk_size`` (and its CUDA
path in fused kernels); the recurrence here is what both compute.  (3) HF
clamps ``dt`` to ``time_step_limit``, which is (0, inf) in the source: no
clamp.  (4) HF returns a router auxiliary loss only when asked
(``output_router_logits``) and the source names no coefficient: the loss
here is the cross entropy alone.  (5) ``forward`` is eager, expert by
expert over the rows that chose it, each expert's rows filled up to a
multiple of 16 with zero rows of zero weight (so that eager compiles a
handful of shapes: ``olmoe_ref.py``); ``loss_and_grads`` must trace under
``jit``, so there every held expert runs on every row under a 0/1 mask
(the same sums).

Parameters come in the program's own tree (``{"params": {"embed",
"layer_<i>": {"mixer_norm", "mamba": {"in_proj", "conv_w", "conv_b",
"A_log", "D", "dt_bias", "norm", "out_proj"} or "attn": {"wq", "wk", "wv",
"wo"}, "mlp_norm", "moe": {"router", "w_gate", "w_up", "w_down"},
"shared_gate", "shared_up", "shared_down"}, "norm_f"}}``) in whatever
dtype the program holds them and are read as float32: weights are data.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * p["scale"].astype(F32)


def _attention(h, p, config):
    b, t, _ = h.shape
    n_q, n_kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config["hidden_size"] // n_q

    def heads(z, n):
        return z.reshape(b, t, n, hd).transpose(0, 2, 1, 3)

    q = heads(h @ p["wq"]["kernel"].astype(F32), n_q)
    k = heads(h @ p["wk"]["kernel"].astype(F32), n_kv)
    v = heads(h @ p["wv"]["kernel"].astype(F32), n_kv)
    k, v = (jnp.repeat(z, n_q // n_kv, axis=1) for z in (k, v))
    scores = config["attention_multiplier"] * (q @ k.transpose(0, 1, 3, 2))
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    att = jax.nn.softmax(scores, axis=-1) @ v
    return att.transpose(0, 2, 1, 3).reshape(b, t, n_q * hd) \
        @ p["wo"]["kernel"].astype(F32)


def _mamba2(h, p, config):
    b, t, _ = h.shape
    nh, hp = config["mamba_n_heads"], config["mamba_d_head"]
    n, taps = config["mamba_d_state"], config["mamba_d_conv"]
    di = nh * hp
    proj = h @ p["in_proj"]["kernel"].astype(F32)
    z, xbc, dt = jnp.split(proj, [di, 2 * di + 2 * n], axis=-1)
    past = jnp.concatenate([jnp.zeros((b, taps - 1, xbc.shape[-1]), F32),
                            xbc], axis=1)
    w = p["conv_w"].astype(F32)
    xbc = jax.nn.silu(sum(past[:, i:i + t] * w[i] for i in range(taps))
                      + p["conv_b"].astype(F32))
    x, b_mat, c_mat = jnp.split(xbc, [di, di + n], axis=-1)
    x = x.reshape(b, t, nh, hp)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))       # [B,T,H]
    a = -jnp.exp(p["A_log"].astype(F32))

    def step(s, at):
        x_t, dt_t, b_t, c_t = at          # [B,H,P] [B,H] [B,N] [B,N]
        s = jnp.exp(dt_t * a)[..., None, None] * s \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        return s, jnp.sum(s * c_t[:, None, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((b, nh, hp, n), F32),
                        tuple(jnp.moveaxis(u, 1, 0)
                              for u in (x, dt, b_mat, c_mat)))
    y = jnp.moveaxis(y, 0, 1) + p["D"].astype(F32)[:, None] * x
    y = y.reshape(b, t, di) * jax.nn.silu(z)
    return _rms_norm(y, p["norm"], float(config["rms_norm_eps"])) \
        @ p["out_proj"]["kernel"].astype(F32)


def _held(config):
    """(first held expert, how many) of the router's experts."""
    return int(config.get("first_local_expert", 0)), \
        int(config["num_local_experts"])


def _route(h, moe, config):
    """h [S, d] -> (weights [S, k], experts [S, k]) over ALL experts."""
    r = h @ moe["router"].astype(F32)
    top, chosen = jax.lax.top_k(r, config["num_experts_per_tok"])
    return jax.nn.softmax(top, axis=-1), chosen


def _expert(h, moe, e):
    gate = h @ moe["w_gate"][e].astype(F32)
    up = h @ moe["w_up"][e].astype(F32)
    return (jax.nn.silu(gate) * up) @ moe["w_down"][e].astype(F32)


def _experts_eager(h, moe, config, block: int = 16):
    """Held expert by held expert over the rows that chose it (concrete
    values), its rows filled to a multiple of ``block`` with a zero row
    of weight zero (benchmark/reference/olmoe_ref.py has the why)."""
    w, chosen = _route(h, moe, config)
    w, chosen = np.asarray(w), np.asarray(chosen)
    first, count = _held(config)
    zero_row = h.shape[0]
    hz = jnp.concatenate([h, jnp.zeros_like(h[:1])])
    y = jnp.zeros_like(hz)
    for e in range(count):
        rows, slot = np.nonzero(chosen == first + e)
        if rows.size:
            fill = -rows.size % block
            at = np.concatenate([rows, np.full(fill, zero_row)])
            weight = np.concatenate([w[rows, slot], np.zeros(fill, w.dtype)])
            y = y.at[at].add(weight[:, None] * _expert(hz[at], moe, e))
    return y[:zero_row]


def _experts_masked(h, moe, config):
    """The same sums with static shapes (traces under jit)."""
    w, chosen = _route(h, moe, config)
    first, count = _held(config)
    total = moe["router"].shape[-1]
    gate = jnp.sum(jax.nn.one_hot(chosen, total, dtype=F32) * w[..., None],
                   axis=1)
    y = jnp.zeros_like(h)
    for e in range(count):
        y = y + gate[:, first + e:first + e + 1] * _expert(h, moe, e)
    return y


def _shared(h, layer):
    gate = h @ layer["shared_gate"]["kernel"].astype(F32)
    up = h @ layer["shared_up"]["kernel"].astype(F32)
    return (jax.nn.silu(gate) * up) \
        @ layer["shared_down"]["kernel"].astype(F32)


def _run(config, params, tokens, experts):
    p = params["params"]
    eps = float(config["rms_norm_eps"])
    res = float(config["residual_multiplier"])
    emb = p["embed"].astype(F32)
    x = float(config["embedding_multiplier"]) * emb[tokens]
    b, t, d = x.shape
    for i, kind in enumerate(
            config["layer_types"][:config["num_hidden_layers"]]):
        layer = p[f"layer_{i}"]
        h = _rms_norm(x, layer["mixer_norm"], eps)
        m = _attention(h, layer["attn"], config) if kind == "attention" \
            else _mamba2(h, layer["mamba"], config)
        x = x + res * m
        h = _rms_norm(x, layer["mlp_norm"], eps).reshape(b * t, d)
        y = experts(h, layer["moe"], config) + _shared(h, layer)
        x = x + res * y.reshape(b, t, d)
    x = _rms_norm(x, p["norm_f"], eps)
    return x @ emb.T / float(config["logits_scaling"])


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
