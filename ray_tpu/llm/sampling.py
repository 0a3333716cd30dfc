"""Token sampling — greedy, temperature, top-k, top-p (nucleus).

One rule, written twice.  The numpy functions (``sample`` and its parts:
pure functions over one 1-D float row) are the plain reference.
``sample_tokens`` is what the engine runs: the same rule as one jitted
program over the device-resident logits of a whole batch, so a decode
step hands the host ``[max_batch]`` token ids and never the
``[max_batch, V]`` logits.  Per-request temperature / top-k / top-p,
seed and token index are DATA of that program, a row each: no mix of
requests compiles anything (ref: vLLM SamplingParams; the reference repo
has no decode path).  It has no sort: the TPU compiler takes 20-30 s to
COMPILE a sort of 50,257 values, so both cut-offs are found by a search
over the value's bit pattern (``keep_mask``; PERF.md, PR 27).
tests/test_llm.py holds the device sampler to the reference.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


@dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling configuration.

    temperature == 0 means greedy (argmax; top_k/top_p ignored).
    top_k == 0 disables top-k; top_p == 1.0 disables nucleus filtering.
    """

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0

    def validate(self) -> None:
        if self.temperature < 0:
            raise ValueError(f"temperature must be >= 0, "
                             f"got {self.temperature}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")


def greedy(logits: np.ndarray) -> int:
    return int(np.argmax(logits))


def apply_temperature(logits: np.ndarray, temperature: float) -> np.ndarray:
    return np.asarray(logits, np.float64) / max(temperature, 1e-8)


def top_k_mask(logits: np.ndarray, k: int) -> np.ndarray:
    """Keep the k highest logits, -inf the rest (k<=0: no-op)."""
    if k <= 0 or k >= logits.shape[-1]:
        return logits
    out = np.array(logits, np.float64)
    kth = np.partition(out, -k)[-k]
    out[out < kth] = -np.inf
    return out


def top_p_mask(logits: np.ndarray, p: float) -> np.ndarray:
    """Nucleus filtering: keep the smallest set of tokens whose
    probability mass reaches ``p`` (always at least one)."""
    if p >= 1.0:
        return logits
    out = np.array(logits, np.float64)
    probs = softmax(out)
    order = np.argsort(-probs, kind="stable")
    cum = np.cumsum(probs[order])
    # Token i survives if the mass BEFORE it is < p (the first token
    # always survives; the one crossing the threshold is included).
    cut = cum - probs[order] >= p
    out[order[cut]] = -np.inf
    return out


def softmax(logits: np.ndarray) -> np.ndarray:
    x = np.asarray(logits, np.float64)
    x = x - np.max(x)
    e = np.exp(x)
    return e / np.sum(e)


def sample(logits: np.ndarray,
           params: Optional[SamplingParams] = None,
           rng: Optional[np.random.Generator] = None) -> int:
    """Sample one token id from a [V] logits row."""
    params = params or SamplingParams()
    if params.temperature <= 0.0:
        return greedy(logits)
    x = apply_temperature(logits, params.temperature)
    x = top_k_mask(x, params.top_k)
    x = top_p_mask(x, params.top_p)
    probs = softmax(x)
    rng = rng or np.random.default_rng()
    return int(rng.choice(probs.shape[-1], p=probs))


# ------------------------------------------------------ on the device

def seed_words(seed: int):
    """Any Python int as the two uint32 words the device sampler keys
    a request by (the low word, the next one)."""
    seed = int(seed)
    return seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF


def pack_rows(rows, max_batch: int):
    """``knobs`` and ``words`` of ``sample_tokens`` for ``rows``, an
    iterable of (SamplingParams, (seed word, seed word), token index);
    the rows behind them are padding: temperature 0, argmax."""
    knobs = np.zeros((max_batch, 2), np.float32)
    knobs[:, 1] = 1.0
    words = np.zeros((max_batch, 4), np.uint32)
    for i, (params, seed, index) in enumerate(rows):
        knobs[i] = params.temperature, params.top_p
        words[i] = min(params.top_k, 0xFFFFFFFF), seed[0], seed[1], index
    return knobs, words


def _threshold(keys, weights, target):
    """Row by row the largest uint32 t with
    sum(weights[keys >= t]) >= target, built from the top bit down: 32
    masked row sums."""
    def bit(i, t):
        cand = t | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        mass = jnp.sum(jnp.where(keys >= cand[:, None], weights, 0.0),
                       axis=1)
        return jnp.where(mass >= target, cand, t)
    return lax.fori_loop(0, 32, bit, jnp.zeros(keys.shape[0], jnp.uint32))


def keep_mask(x, top_k, top_p):
    """[B, V] bool: what ``top_k_mask`` then ``top_p_mask`` leave of the
    float32 rows ``x`` (already divided by the temperature).  Top-k keeps
    every value >= the k-th largest (ties stay; k <= 0 or >= V: all).
    The nucleus keeps the largest values while the mass above them is
    under ``top_p`` (the crossing token included, at least one; top_p >=
    1: all).  Both cut-offs are searched for, not sorted for: exact for
    top-k; for the nucleus the sorted prefix wherever no tie sits on its
    boundary (tokens tied with the last kept one ALL stay, where the
    reference keeps those of lower index)."""
    vocab = x.shape[-1]
    x = jnp.where(x == 0, 0.0, x)           # -0.0 ties with 0.0
    # float32 -> uint32 in the same order
    bits = lax.bitcast_convert_type(x, jnp.uint32)
    keys = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))
    k = jnp.where((top_k <= 0) | (top_k >= vocab), vocab, top_k)
    kth = _threshold(keys, jnp.ones_like(x), k.astype(jnp.float32))
    keep = keys >= kth[:, None]
    e = jnp.where(keep, jnp.exp(x - jnp.max(x, axis=1, keepdims=True)), 0.0)
    cut = _threshold(keys, e, top_p * jnp.sum(e, axis=1))
    return keep & ((keys >= cut[:, None]) | (top_p >= 1.0)[:, None])


def sample_tokens(logits, knobs, words):
    """``logits[B, 1, V]``, ``knobs[B, 2]``, ``words[B, 4]`` -> int32[B]:
    the rule of ``sample`` above, row by row, in float32 on the forward's
    own float32 logits.  A row's parameters are data, in two arrays
    because every array is a transfer of its own: ``knobs`` float32
    (temperature, top_p) and ``words`` uint32 (top_k, the two seed words,
    the token's index); ``pack_rows`` fills them.

    A row with temperature 0 (a padded row too) is ``argmax``, lowest
    index on a tie.  Any other row is divided by max(T, 1e-8), cut by
    ``keep_mask`` and drawn from once (Gumbel-max) with the key
    ``fold_in(key(seed words), index)``: a request's tokens depend on
    its seed and the token's index alone, not on its slot or its
    neighbours.  Every operation is row-wise: a row of padding or NaN
    disturbs no other.  A batch with no sampled row skips the search and
    the noise (``lax.cond``: one program, both branches compiled)."""
    with jax.named_scope("sample"):
        temperature, top_p = knobs[:, 0], knobs[:, 1]
        top_k = words[:, 0].astype(jnp.int32)
        rows = logits[:, 0].astype(jnp.float32)
        greedy = jnp.argmax(rows, axis=-1).astype(jnp.int32)

        def drawn():
            x = rows / jnp.maximum(temperature, 1e-8)[:, None]
            keys = jax.vmap(lambda seed, index: jax.random.fold_in(
                jax.random.wrap_key_data(seed, impl="threefry2x32"),
                index))(words[:, 1:3], words[:, 3])
            token = jax.vmap(jax.random.categorical)(
                keys, jnp.where(keep_mask(x, top_k, top_p), x, -jnp.inf))
            return jnp.where(temperature > 0, token.astype(jnp.int32),
                             greedy)

        return lax.cond(jnp.any(temperature > 0), drawn, lambda: greedy)


def jit_sampler(max_batch: int):
    """The engine's two jitted programs: ``sample_tokens``, and
    ``last_rows(logits[1, 1, V]) -> [max_batch, 1, V]``, which puts the
    one row a prefill's forward returns (its prompt's last position,
    llm/engine.py ``jit_forward``) into row 0 of zeros of the decode
    shape, so that ``sample_tokens`` is compiled for ONE shape, and
    ``last_rows`` for one too, whatever the prefill's bucket."""
    def last_rows(logits):
        with jax.named_scope("sample"):
            return jnp.pad(logits, ((0, max_batch - 1), (0, 0), (0, 0)))

    return jax.jit(sample_tokens), jax.jit(last_rows)


def jit_feed():
    """The engine's ``feed(tokens[B, 1], ids[B], rows[B]) -> tokens``:
    ``ids[j]`` put at row ``rows[j]`` (an index outside the batch: that
    id is dropped), the other rows kept.  It keeps every row's latest
    token id on the device, where the next decode step takes it, so that
    a launch never waits for the host's copy of the ids: a decode step's
    ids go row for row (``rows = arange(B)``), a prefill's row 0 to the
    row its sequence runs in."""
    def feed(tokens, ids, rows):
        with jax.named_scope("sample"):
            return tokens.at[rows, 0].set(ids, mode="drop")

    return jax.jit(feed)
