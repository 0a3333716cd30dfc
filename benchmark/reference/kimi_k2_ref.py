"""Kimi-K2 (HF ``model_type`` kimi_k2; moonshotai/Kimi-K2.5's language
model, whose layer is the DeepSeek-V3 block) in plain float32
``jax.numpy``: forward, training loss and gradients.  No flax, no cache,
no latent-space attention, no sort, no grouped matmul: every head's keys
and values are expanded and attended as the equations say, each token's
experts by the layer equations.

``RMSNorm(x) = x / sqrt(mean(x^2) + rms_norm_eps) * g``.  ``x = E[tokens]``.
Layer ``l``: ``h = x + attn_l(RMSNorm(x))``; ``x = h + ffn_l(RMSNorm(h))``.
Output: ``RMSNorm(x) W_head`` (untied).  No bias anywhere.

With ``H`` heads, ranks ``r_q`` / ``r_kv``, a head's widths ``d_n``
(``qk_nope_head_dim``) | ``d_r`` (``qk_rope_head_dim``) | ``d_v``:

- ``c_q = RMSNorm(u W_qa)``; ``q = c_q W_qb`` -> [H, d_n + d_r] = ``q_nope |
  q_pe``.
- ``u W_kva`` [r_kv + d_r] = ``c | k_pe``; ``c_kv = RMSNorm(c)``; ``k_pe`` is
  one vector shared by the heads.  ``c_kv W_kvb`` -> [H, d_n + d_v] =
  ``k_nope | v``.
- RoPE on ``q_pe`` and ``k_pe`` over their ``d_r`` dimensions with YaRN's
  inverse frequencies: ``f_i = theta ** (-2i / d_r)``; the correction
  dimensions ``c(rot) = d_r ln(L / (2 pi rot)) / (2 ln theta)`` for ``rot =
  beta_fast`` (floored) and ``beta_slow`` (ceiled), clipped to 0 .. d_r - 1,
  ``L = original_max_position_embeddings``; ``m_i = 1 - clip((i - low) /
  (high - low), 0, 1)``; ``inv_freq_i = f_i / factor * (1 - m_i) + f_i *
  m_i``.  cos and sin are multiplied by ``yarn_mscale(factor, mscale) /
  yarn_mscale(factor, mscale_all_dim)`` with ``yarn_mscale(s, m) = 0.1 m
  ln(s) + 1`` (1 for the published config: both are 1).
- scores ``(q_nope . k_nope + q_pe . k_pe) * s``, ``s = (d_n + d_r) ** -0.5 *
  yarn_mscale(factor, mscale_all_dim) ** 2``; causal softmax; ``sum p v``
  [H, d_v] -> ``W_o``.
- Dense FFN (``l < first_k_dense_replace``): ``(silu(x W1) * (x W3)) W2`` at
  ``intermediate_size``.
- Sparse FFN (the rest): ``shared(x) + routed(x)``.  ``shared`` is a SwiGLU
  of width ``moe_intermediate_size * n_shared_experts``.  ``s = sigmoid(x
  W_g)`` over ALL ``published.n_routed_experts`` experts (the file's
  ``n_routed_experts`` where there is no ``published`` group); chosen: the
  ``num_experts_per_tok`` largest of ``s + e_score_correction_bias`` (ties
  to the lower index; ``topk_method`` noaux_tc with ``n_group`` = ``topk_group``
  = 1: one group holding every expert, so the grouped choice is the plain
  one, and another value is refused); weights ``w_i = s_i / (sum_chosen s +
  1e-20)`` (``norm_topk_prob``; the bias is NOT in the weight) times
  ``routed_scaling_factor``; ``routed = sum_i w_i (silu(x W1_i) * (x W3_i))
  W2_i`` at ``moe_intermediate_size``, over the chosen experts that are HELD
  (``first_routed_expert`` .. + ``n_routed_experts``: one chip's share under
  expert parallelism; what the absent ones would add is left out, as the
  program leaves it out).

Departures from HF's ``modeling_deepseek.py`` (written down from memory:
there is no network here), noted: (1) the source stores ``q_pe`` / ``k_pe``
with a rotary pair's two numbers ADJACENT (interleaved) and permutes them
to halves before it rotates; here they are rotated as they lie in the
rotate-half convention (dimension i with i + d_r/2), as the program does.
With seeded weights the two differ by a fixed permutation of ``W_qb``'s and
``W_kva``'s rope columns and by nothing else (``assumed.rope_pairing``).
(2) HF moves ``e_score_correction_bias`` by the experts' load outside
autograd; here it is data (the leaf ``expert_bias``) and takes no gradient.
(3) ``forward`` is eager, attention in blocks of ``ATTN_BLOCK`` query
positions (so 4,096 positions fit: one block's scores are H x block x T
float32) and expert by expert over the rows that chose it, each expert's
rows filled up to a multiple of 16 with zero rows of zero weight (so that
eager compiles a handful of shapes: ``olmoe_ref.py``); ``loss_and_grads``
must trace under ``jit``, so there attention is whole and every held expert
runs on every row under a 0/1 mask (the same sums).

Parameters come in the program's own tree (``{"params": {"embed", "lm_head",
"layer_<i>": {"attn_norm", "attn": {"wq_a", "q_norm", "wq_b", "wkv_a",
"kv_norm", "wkv_b" [r_kv, H * (d_n + d_v)], "wo"}, "mlp_norm", "w_gate",
"w_up", "w_down" (a dense layer) or "moe": {"router", "expert_bias",
"w_gate", "w_up", "w_down"} with "shared_gate", "shared_up",
"shared_down"}, "norm_f"}}``) in whatever dtype the program holds them and
are read as float32, a layer's matrices at a time: weights are data.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
ATTN_BLOCK = 256
ROUTE_NORM_EPS = 1e-20


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * p["scale"].astype(F32)


def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(config: dict) -> np.ndarray:
    """[d_r / 2] float32, by the equations of the module docstring."""
    d_r, theta = config["qk_rope_head_dim"], float(config["rope_theta"])
    rs = config["rope_scaling"]
    if rs["type"] != "yarn":
        raise ValueError("the reference writes YaRN down, not "
                         f"{rs['type']!r}")
    length = rs["original_max_position_embeddings"]

    def correction(rot):
        return d_r * math.log(length / (rot * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction(rs["beta_fast"])), 0)
    high = min(math.ceil(correction(rs["beta_slow"])), d_r - 1)
    if low == high:
        high += 0.001
    i = np.arange(d_r // 2, dtype=np.float32)
    f = theta ** (-2.0 * i / d_r)
    m = 1.0 - np.clip((i - low) / (high - low), 0.0, 1.0)
    return (f / rs["factor"] * (1.0 - m) + f * m).astype(np.float32)


def softmax_scale(config: dict) -> float:
    rs = config["rope_scaling"]
    m = yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    return (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]) ** -0.5 \
        * m * m


def _rope(x, config):
    """x [B, T, ..., d_r]: rotate-half rotary embedding at positions 0..T-1
    (the axes between T and d_r are heads, or none)."""
    rs = config["rope_scaling"]
    t, d = x.shape[1], x.shape[-1]
    angles = jnp.arange(t, dtype=F32)[:, None] * yarn_inv_freq(config)
    mult = yarn_mscale(rs["factor"], rs["mscale"]) \
        / yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    shape = (1, t) + (1,) * (x.ndim - 3) + (d // 2,)
    cos = (jnp.cos(angles) * mult).reshape(shape)
    sin = (jnp.sin(angles) * mult).reshape(shape)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1)


def _attention(u, p, config, block):
    b, t, _ = u.shape
    h = config["num_attention_heads"]
    r_kv = config["kv_lora_rank"]
    d_n, d_r, d_v = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                     config["v_head_dim"])
    eps = float(config["rms_norm_eps"])
    c_q = _rms_norm(u @ p["wq_a"]["kernel"].astype(F32), p["q_norm"], eps)
    q = (c_q @ p["wq_b"]["kernel"].astype(F32)).reshape(b, t, h, d_n + d_r)
    q_nope, q_pe = q[..., :d_n], _rope(q[..., d_n:], config)
    ckv = u @ p["wkv_a"]["kernel"].astype(F32)
    c_kv = _rms_norm(ckv[..., :r_kv], p["kv_norm"], eps)
    k_pe = _rope(ckv[..., r_kv:], config)                     # [B, T, d_r]
    kv = (c_kv @ p["wkv_b"].astype(F32)).reshape(b, t, h, d_n + d_v)
    k_nope, v = kv[..., :d_n], kv[..., d_n:]
    scale = softmax_scale(config)
    outs = []
    for lo in range(0, t, block or t):
        hi = min(lo + (block or t), t)
        scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope[:, lo:hi],
                             k_nope[:, :hi])
                  + jnp.einsum("bqhd,bkd->bhqk", q_pe[:, lo:hi],
                               k_pe[:, :hi])) * scale
        causal = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        scores = jnp.where(causal, scores, -jnp.inf)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd",
                               jax.nn.softmax(scores, axis=-1), v[:, :hi]))
    att = jnp.concatenate(outs, axis=1).reshape(b, t, h * d_v)
    return att @ p["wo"]["kernel"].astype(F32)


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate.astype(F32)) * (h @ up.astype(F32))) \
        @ down.astype(F32)


def _held(config):
    """(first held expert, how many) of the router's experts."""
    return int(config.get("first_routed_expert", 0)), \
        int(config["n_routed_experts"])


def _route(h, moe, config):
    """h [S, d] -> (weights [S, k], experts [S, k]) over ALL the router's
    experts."""
    if config.get("n_group", 1) != 1 or config.get("topk_group", 1) != 1:
        raise ValueError("grouped routing is not written down: the "
                         "published config has n_group = topk_group = 1")
    if config.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError(f"scoring_func {config['scoring_func']!r}")
    s = jax.nn.sigmoid(h @ moe["router"].astype(F32))
    _, chosen = jax.lax.top_k(s + moe["expert_bias"].astype(F32),
                              config["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if config.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + ROUTE_NORM_EPS)
    return w * float(config.get("routed_scaling_factor", 1.0)), chosen


def _expert(h, moe, e):
    return _swiglu(h, moe["w_gate"][e], moe["w_up"][e], moe["w_down"][e])


def _experts_eager(h, moe, config, block: int = 16):
    """Held expert by held expert over the rows that chose it (concrete
    values), its rows filled to a multiple of ``block`` with a zero row of
    weight zero (benchmark/reference/olmoe_ref.py has the why)."""
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
    n = moe["router"].shape[-1]
    gate = jnp.sum(jax.nn.one_hot(chosen, n, dtype=F32) * w[..., None],
                   axis=1)
    y = jnp.zeros_like(h)
    for e in range(count):
        y = y + gate[:, first + e:first + e + 1] * _expert(h, moe, e)
    return y


def _run(config, params, tokens, experts, block):
    p = params["params"]
    eps = float(config["rms_norm_eps"])
    x = p["embed"].astype(F32)[tokens]
    b, t, d = x.shape
    for i in range(config["num_hidden_layers"]):
        layer = p[f"layer_{i}"]
        x = x + _attention(_rms_norm(x, layer["attn_norm"], eps),
                           layer["attn"], config, block)
        h = _rms_norm(x, layer["mlp_norm"], eps).reshape(b * t, d)
        if i < config["first_k_dense_replace"]:
            y = _swiglu(h, *(layer[k]["kernel"]
                             for k in ("w_gate", "w_up", "w_down")))
        else:
            y = experts(h, layer["moe"], config) + _swiglu(
                h, *(layer[k]["kernel"] for k in
                     ("shared_gate", "shared_up", "shared_down")))
        x = x + y.reshape(b, t, d)
    return _rms_norm(x, p["norm_f"], eps) @ p["lm_head"].astype(F32)


def forward(config: dict, params, tokens):
    """tokens [B, T] int -> logits [B, T, vocab] float32 (eager)."""
    with jax.default_matmul_precision("highest"):
        return _run(config, params, tokens, _experts_eager, ATTN_BLOCK)


def loss(config: dict, params, tokens):
    """Mean next-token cross entropy over tokens [B, T+1]."""
    with jax.default_matmul_precision("highest"):
        logits = _run(config, params, tokens[:, :-1], _experts_masked, 0)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None],
                                             axis=-1))


def loss_and_grads(config: dict, params, tokens):
    return jax.value_and_grad(lambda q: loss(config, q, tokens))(params)
