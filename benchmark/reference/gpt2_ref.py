"""GPT-2 (Radford et al. 2019) in plain float32 ``jax.numpy``: forward,
next-token loss and gradients.  No kernels, no cache, no batching tricks,
no flax: the layer equations written down, so that the program can be
held to them.

Follows the released model: learned token and position embeddings summed;
per block, pre-LayerNorm causal multi-head self-attention with biased
projections and a residual, then a pre-LayerNorm MLP of width n_inner with
the tanh approximation of GELU ("gelu_new") and a residual; a final
LayerNorm; logits through the transposed token embedding (tied).
LayerNorm uses the configuration's epsilon (1e-5).

Departure noted: the program's LayerNorm (flax's default) uses 1e-6.  On
activations of variance ~1 the two differ by ~5e-6 relative, far inside
every tolerance here; it is listed in PERF.md for a later PR.

Parameters come in the program's own tree (``{"params": {"wte", "wpe",
"h_<i>": {"ln_1", "c_attn", "c_proj", "ln_2", "mlp_in", "mlp_out"},
"ln_f"}}``): weights are data, made from the seed by the program's init.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"].astype(F32) \
        + p["bias"].astype(F32)


def _dense(x, p):
    return x @ p["kernel"].astype(F32) + p["bias"].astype(F32)


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _attention(x, block, n_head):
    b, t, d = x.shape
    hd = d // n_head
    qkv = _dense(x, block["c_attn"])
    q, k, v = jnp.split(qkv, 3, axis=-1)

    def heads(z):
        return z.reshape(b, t, n_head, hd).transpose(0, 2, 1, 3)

    q, k, v = heads(q), heads(k), heads(v)
    scores = q @ k.transpose(0, 1, 3, 2) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    att = jax.nn.softmax(scores, axis=-1) @ v
    att = att.transpose(0, 2, 1, 3).reshape(b, t, d)
    return _dense(att, block["c_proj"])


def forward(config: dict, params, tokens):
    """tokens [B, T] int -> logits [B, T, vocab] float32."""
    with jax.default_matmul_precision("highest"):
        p = params["params"]
        eps = float(config.get("layer_norm_epsilon", 1e-5))
        t = tokens.shape[1]
        x = p["wte"].astype(F32)[tokens] + p["wpe"].astype(F32)[:t]
        for i in range(config["n_layer"]):
            block = p[f"h_{i}"]
            x = x + _attention(_layer_norm(x, block["ln_1"], eps), block,
                               config["n_head"])
            y = _layer_norm(x, block["ln_2"], eps)
            y = _dense(_gelu_new(_dense(y, block["mlp_in"])),
                       block["mlp_out"])
            x = x + y
        x = _layer_norm(x, p["ln_f"], eps)
        return x @ p["wte"].astype(F32).T


def loss(config: dict, params, tokens):
    """Mean next-token cross entropy over tokens [B, T+1]."""
    logits = forward(config, params, tokens[:, :-1])
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked)


def loss_and_grads(config: dict, params, tokens):
    return jax.value_and_grad(lambda q: loss(config, q, tokens))(params)
