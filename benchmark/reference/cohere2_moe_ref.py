"""Cohere2-MoE (HF ``model_type`` cohere2_moe; CohereLabs'
``command-a-plus-05-2026``, Command A+) in plain float32 ``jax.numpy``:
forward, training loss and gradients.  No flax, no kernel, no cache, no
ring, no batching of requests, no sort, no grouped matmul: every layer sees
every position and masks what its kind may not see, each token's experts by
the layer equations.

``LN(x) = (x - mean x) / sqrt(var x + layer_norm_eps) * g`` (no bias).  ``x =
E[tokens]``.  Layer ``l`` (``use_parallel_block``): ``y = LN(x)``; ``x = x +
attn_l(y) + routed_l(y) + shared_l(y)``.  Output: ``LN(x) E^T * logit_scale``
(``tie_word_embeddings``).  No bias anywhere.

- ``q = y W_q`` as ``num_attention_heads`` heads of ``head_dim``, ``k = y
  W_k``, ``v = y W_v`` as ``num_key_value_heads`` heads; query head ``h``
  meets K/V head ``h // (heads / kv heads)``.
- ``sliding_attention``: q and k turned by RoPE over the whole head
  (``rotary_pct`` 1), ADJACENT pairs (``position_embedding_type``
  rope_gptj): ``(x_2i, x_2i+1) -> (x_2i cos - x_2i+1 sin, x_2i sin + x_2i+1
  cos)`` at the angle ``p * rope_theta ** (-2i / head_dim)``; key ``j``
  visible to query ``i`` iff ``0 <= i - j < sliding_window``.
  ``full_attention``: NO rotation; visible iff ``j <= i``.
- scores ``q . k * head_dim ** -0.5``, softmax, ``sum p v`` -> ``W_o``.
- ``s = sigmoid(y W_r)`` over ALL ``published.num_experts`` experts (the
  file's ``num_experts`` where there is no ``published`` group); chosen: the
  ``num_experts_per_tok`` largest (ties to the lower index); weights ``w_e =
  s_e / sum_chosen s`` (``norm_topk_prob``); ``routed = sum_e w_e (silu(y
  Wg_e) * (y Wu_e)) Wd_e`` at ``intermediate_size``, over the chosen experts
  that are HELD (``first_expert`` .. + ``num_experts``: one chip's share
  under expert parallelism; what the absent ones would add is left out, as
  the program leaves it out).
- ``shared = 1 / n sum_s (silu(y Wg_s) * (y Wu_s)) Wd_s``, ``n =
  num_shared_experts`` SwiGLUs of ``intermediate_size``
  (``shared_expert_combination_strategy`` average), read from the program's
  ONE SwiGLU of ``n x intermediate_size``: shared expert ``s`` is columns ``s
  * width .. (s + 1) * width`` of ``shared_gate`` / ``shared_up`` and the
  same rows of ``shared_down``, and the mean is taken here expert by expert.

Departures from the source's modeling file (not at hand: there is no network
here; written down from the catalog's config and ``described_as``), noted:
(1) what ``assumed`` in benchmark/configs/command-a-plus-05-2026.json lists
(one expert's width, the mean of the shared experts ADDED to the routed sum,
the router on the same ``y``, no rotation in the global layers, no vision
tower).  (2) ``forward`` is eager, attention in blocks of ``ATTN_BLOCK``
query positions, one after the other, against the keys their kind lets them
see (so 16,384 positions fit: one block's scores are H x block x keys
float32; a sliding layer's block takes the slice of keys that can reach it)
and, eager,
expert by expert over the rows that chose it, each expert's rows filled up
to a multiple of 16 with zero rows of zero weight (``olmoe_ref.py`` has the
why); with ``by_layer`` each layer runs under ``jit`` (one program a kind of
layer), and there, as in ``loss_and_grads``, every held expert runs on every
row under a 0/1 mask (the same sums).

Parameters come in the program's own tree (``{"params": {"embed",
"layer_<i>": {"norm", "attn": {"wq", "wk", "wv", "wo"}, "moe": {"router",
"w_gate", "w_up", "w_down"}, "shared_gate", "shared_up", "shared_down"},
"norm_f"}}``) in whatever dtype the program holds them and are read as
float32, a matrix at a time: weights are data.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
ATTN_BLOCK = 128
KINDS = ("sliding_attention", "full_attention")


def _layer_norm(x, p, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * p["scale"].astype(F32)


def _dense(x, p):
    return x @ p["kernel"].astype(F32)


def _rope(x, theta: float):
    """x [B, T, H, D] at positions 0 .. T-1, adjacent pairs."""
    t, d = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    angles = jnp.arange(t, dtype=F32)[:, None] * freqs[None, :]
    cos, sin = (f(angles)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def _attention(y, p, config, kind: str, block):
    """Blocks of ``block`` query positions, one after the other
    (``lax.map``: one block's scores at a time), each against the keys its
    kind lets it see: every earlier key (full), or the slice of ``window +
    block`` keys that ends with the block (sliding); the mask is by
    position.  ``block`` 0: all positions at once."""
    b, t, _ = y.shape
    h, h_kv = config["num_attention_heads"], config["num_key_value_heads"]
    dh = config["head_dim"]
    q = _dense(y, p["wq"]).reshape(b, t, h, dh)
    k = _dense(y, p["wk"]).reshape(b, t, h_kv, dh)
    v = _dense(y, p["wv"]).reshape(b, t, h_kv, dh)
    window = None
    if kind == "sliding_attention":
        window = int(config["sliding_window"])
        q, k = (_rope(z, float(config["rope_theta"])) for z in (q, k))
    block = min(block or t, t)
    pad = -t % block                # rows behind the last: seen by no one
    q, k, v = (jnp.pad(z, ((0, 0), (0, pad), (0, 0), (0, 0)))
               for z in (q, k, v))
    q = q.reshape(b, t + pad, h_kv, h // h_kv, dh)
    keys = t + pad if window is None else min(t + pad, window + block)

    def attend(lo):
        first = jnp.clip(lo + block - keys, 0, t + pad - keys)
        q_b = jax.lax.dynamic_slice_in_dim(q, lo, block, axis=1)
        k_b = jax.lax.dynamic_slice_in_dim(k, first, keys, axis=1)
        v_b = jax.lax.dynamic_slice_in_dim(v, first, keys, axis=1)
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", q_b, k_b) * dh ** -0.5
        behind = (lo + jnp.arange(block))[:, None] \
            - (first + jnp.arange(keys))[None, :]
        seen = behind >= 0
        if window is not None:
            seen = seen & (behind < window)
        scores = jnp.where(seen, scores, -jnp.inf)
        return jnp.einsum("bgrqk,bkgd->bqgrd",
                          jax.nn.softmax(scores, axis=-1), v_b)

    out = jax.lax.map(attend, jnp.arange(0, t + pad, block))
    out = jnp.moveaxis(out, 0, 1).reshape(b, t + pad, h * dh)[:, :t]
    return _dense(out, p["wo"])


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate.astype(F32)) * (h @ up.astype(F32))) \
        @ down.astype(F32)


def _held(config):
    """(first held expert, how many) of the router's experts."""
    return int(config.get("first_expert", 0)), int(config["num_experts"])


def _route(h, moe, config):
    """h [S, d] -> (weights [S, k], experts [S, k]) over ALL the router's
    experts."""
    s = jax.nn.sigmoid(h @ moe["router"].astype(F32))
    w, chosen = jax.lax.top_k(s, config["num_experts_per_tok"])
    return w / jnp.sum(w, axis=-1, keepdims=True), chosen


def _expert(h, moe, e):
    return _swiglu(h, moe["w_gate"][e], moe["w_up"][e], moe["w_down"][e])


def _experts_eager(h, moe, config, block: int = 16):
    """Held expert by held expert over the rows that chose it (concrete
    values), its rows filled to a multiple of ``block`` with a zero row of
    weight zero."""
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


def _shared(h, layer, config):
    """The mean of the shared experts, each a SwiGLU of one expert's
    width: slices of the program's one wide SwiGLU."""
    n, width = config["num_shared_experts"], config["intermediate_size"]
    gate, up, down = (layer[k]["kernel"] for k in
                      ("shared_gate", "shared_up", "shared_down"))
    total = 0.0
    for s in range(n):
        cols = slice(s * width, (s + 1) * width)
        total = total + _swiglu(h, gate[:, cols], up[:, cols], down[cols])
    return total / n


def _layer(config, kind: str, layer, x, experts, block):
    """One parallel block: ``x + attn(y) + routed(y) + shared(y)``."""
    b, t, d = x.shape
    y = _layer_norm(x, layer["norm"], float(config["layer_norm_eps"]))
    h = y.reshape(b * t, d)
    ffn = experts(h, layer["moe"], config) + _shared(h, layer, config)
    return x + _attention(y, layer["attn"], config, kind, block) \
        + ffn.reshape(b, t, d)


@functools.lru_cache(maxsize=None)
def _compiled_layer(config_json: str, kind: str):
    """``_layer`` under ``jit``, one program a kind of layer."""
    config = json.loads(config_json)
    return jax.jit(lambda layer, x: _layer(config, kind, layer, x,
                                           _experts_masked, ATTN_BLOCK))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(x, scale, embed, eps, logit_scale):
    return _layer_norm(x, {"scale": scale}, eps) @ embed.astype(F32).T \
        * logit_scale


def _check(config):
    kinds = config["layer_types"]
    if len(kinds) != config["num_hidden_layers"] or set(kinds) - set(KINDS):
        raise ValueError("layer_types must have num_hidden_layers entries "
                         f"of {KINDS}")
    same = {"hidden_act": "silu", "attention_bias": False,
            "tie_word_embeddings": True, "use_parallel_block": True,
            "use_qk_norm": False, "expert_selection_fn": "sigmoid",
            "norm_topk_prob": True, "rotary_pct": 1,
            "position_embedding_type": "rope_gptj",
            "shared_expert_combination_strategy": "average",
            "first_k_dense_replace": 0, "use_gated_activation": True}
    bad = {k: config.get(k) for k, v in same.items() if config.get(k) != v}
    if bad:
        raise ValueError("the reference writes the published choices down, "
                         f"not their alternatives: {bad}")
    return kinds


def _run(config, params, tokens, experts, block, last=0, lengths=None,
         by_layer=False):
    p = params["params"]
    eps = float(config["layer_norm_eps"])
    kinds = _check(config)
    x = p["embed"][tokens].astype(F32)
    key = json.dumps(config, sort_keys=True)
    for i, kind in enumerate(kinds):
        if by_layer:
            x = _compiled_layer(key, kind)(p[f"layer_{i}"], x)
        else:
            x = _layer(config, kind, p[f"layer_{i}"], x, experts, block)
    if lengths is not None:     # the last positions of each row's OWN length
        at = jnp.asarray(lengths)[:, None] - last + jnp.arange(last)
        x = jnp.take_along_axis(x, at[..., None], axis=1)
    else:
        x = x[:, -last:]
    scale = float(config.get("logit_scale", 1))
    if by_layer:
        return _head(x, p["norm_f"]["scale"], p["embed"], eps, scale)
    return _layer_norm(x, p["norm_f"], eps) @ p["embed"].astype(F32).T \
        * scale


def forward(config: dict, params, tokens, last: int = 0, lengths=None,
            by_layer: bool = False):
    """tokens [B, T] int -> logits [B, T, vocab] float32 (eager); ``last``
    > 0: of the last ``last`` positions alone, with ``lengths`` [B] those
    that end at each row's own length (the rows filled behind it to one T:
    what lies behind a position changes nothing before it).  ``by_layer``:
    each layer under ``jit``, one program a kind of layer."""
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
