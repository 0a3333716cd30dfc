"""Mixture-of-Experts feed-forward: dropless token-choice routing as
sorted, grouped matmuls.

ONE path, for every model that has experts (OLMoE through
``models/llama.py``; GPT-2's synthetic ``moe_num_experts`` option as its
ungated case):

- ``moe.route``: router logits, a score for each of ALL the experts
  (``scoring``: ``softmax`` over them, or ``sigmoid`` of each logit
  alone) and top-k, in float32 (a bf16 near-tie must not flip an
  expert).  WHICH experts and WHAT weight may come from different
  numbers: with a selection bias (``select_bias``: the leaf
  ``expert_bias`` [E], which no gradient trains) the k largest of
  ``score + bias`` are chosen, and their weights are the scores alone.
  The weights are renormalised over the chosen k only where the model
  says so (``norm_topk_prob``), each family in its own form (``w / sum
  w``, or ``w / (sum w + norm_eps)``), and a family that scales its
  routed experts' sum multiplies them by its ``routed_scaling_factor``
  (``MoEMLP``'s field; 1, and then no operation, for the others);
- ``moe.dispatch``: the ``S x k`` (token, expert) pairs are flattened
  and sorted by expert with a STABLE sort, so a pair's place depends on
  nothing but the pairs before it; the rows are gathered in that order;
- ``moe.experts``: the expert matmuls run as grouped matmuls over the
  sorted rows with the group sizes from the routing
  (``jax.lax.ragged_dot``: on the TPU the compiler lowers it to its own
  Mosaic grouped-matmul kernel, which reads the matrices of the experts
  that have rows and no others; PERF.md has the measurement);
- ``moe.combine``: un-sort, weight, sum over k.

No capacity, no one-hot dispatch, no dropped token: every pair is
computed, and the executed expert FLOPs are ``S x k`` rows' worth.  Rows
marked invalid (the decode batch's padding: negative positions) are sent
to no expert and count nowhere.  Differentiable end to end (the trainer
runs the same op).

A layer may hold a SHARE of its experts (``first_expert``,
``held_experts``: a contiguous run of the ``num_experts`` the router
scores; one chip's part under expert parallelism).  It routes over all of
them, sends the pairs of the experts it does not hold where the invalid
rows' pairs go, to no group, and returns its own experts' part of the
result; its matrices, ``load`` and ``prob_mean`` are of the held experts
only.  Nothing stands in for the absent ones: the shares of all the chips
add up to the whole layer (tests/test_granite.py).  Holding all of them
is the default.

Where a layer holds a share and the input is large (a prefill),
dispatch, experts and combine run over a static CAPACITY of rows and not
over ``S x k``: the sort is stable and a pair without an expert here
carries the largest key, so the held pairs are exactly the first
``sum(load)`` entries of the sorted order, and the head of it is all
there is to gather, multiply and add back: by token, as a matmul with
each row's weight at its token, summed in float32.  In one block that
matmul is ``[S, capacity] x [capacity, d]`` and grows with the square of
the rows; where that costs (``combine_blocks``: from ``S``, ``k`` and the
capacity alone) the head is sorted once more, by token, and each block of
128 tokens multiplies the window of ``128 x k`` rows that holds its own,
so the combine grows with ``S``.  The capacity is twice the balanced
share, ``2 x S x k x held / num_experts``, rounded up to whole tiles of
128 rows (``compact_capacity``: one tile more where the grouped matmul
would take 512-row tiles), and the branch exists only where that is at
most half of ``S x k``: never in a layer that holds all its experts,
never in a decode step.  It is taken under
``jax.lax.cond(sum(load) <= capacity, ...)``: a router that crowds onto
the held experts falls back to the path over all ``S x k`` rows for that
layer and that call, so every pair is still computed, and the op stays
differentiable.  Each call sows ``compact`` (bool: it took the compact
branch) beside ``load``.

Each layer sows ``moe`` into flax's ``intermediates``: ``load`` [E] (pairs
per expert), ``prob_mean`` [E] (the mean score; under ``sigmoid`` scoring
the scores of a row do not add up to 1) and ``z`` (mean squared
log-sum-exp of the router logits: under ``softmax`` the squared log of the
scores' normaliser, the z-loss; under ``sigmoid`` nothing normalises the
scores and ``z`` is the same statistic of the logits, a measure of their
size that no loss uses), from which come the training losses
(``moe_losses``) and the engine's counters (``moe_counters``).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp


def route(logits, k: int, norm_topk_prob: bool, scoring: str = "softmax",
          select_bias=None, norm_eps: float = 0.0):
    """Router logits [S, E] (float32) -> (weights [S, k], experts [S, k],
    scores [S, E]).  The scores: ``softmax`` over all experts, or the
    ``sigmoid`` of each logit.  The experts: the k largest scores, or
    with ``select_bias`` [E] the k largest of ``score + bias``, ties to
    the lower index.  The weights: the chosen experts' scores, the bias
    NOT in them; with ``norm_topk_prob`` divided by their sum (+
    ``norm_eps`` where a family has one)."""
    if scoring not in ("softmax", "sigmoid"):
        raise ValueError(f"scoring {scoring!r}")
    scores = jax.nn.softmax(logits, axis=-1) if scoring == "softmax" \
        else jax.nn.sigmoid(logits)
    if select_bias is None:
        weights, experts = jax.lax.top_k(scores, k)
    else:
        _, experts = jax.lax.top_k(scores + select_bias, k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
    if norm_topk_prob:
        total = jnp.sum(weights, axis=-1, keepdims=True)
        weights = weights / (total + norm_eps if norm_eps else total)
    return weights, experts, scores


def grouped_ffn(rows, group_sizes, w_gate, w_in, w_out, act: Callable):
    """The experts over rows sorted by expert: ``act(rows @ w_gate[e]) *
    (rows @ w_in[e])`` (ungated where ``w_gate`` is None), then
    ``@ w_out[e]``, each a grouped matmul."""
    h = jax.lax.ragged_dot(rows, w_in, group_sizes)
    if w_gate is not None:
        h = act(jax.lax.ragged_dot(rows, w_gate, group_sizes)) * h
    else:
        h = act(h)
    return jax.lax.ragged_dot(h, w_out, group_sizes)


ROW_TILE = 128


def compact_capacity(pairs: int, held: int, num_experts: int
                     ) -> Optional[int]:
    """Rows the compact path runs over where a layer that holds ``held``
    of ``num_experts`` routes ``pairs`` (S x k) pairs, or None where the
    path does not exist.  From the shapes alone: no knob, no model's
    name.

    Twice the balanced share, ``2 x pairs x held / num_experts``, rounded
    up to whole tiles of 128 rows (the least row block the compiler's
    grouped matmul takes).  The path exists where that is at most half
    the pairs and a true share is held: never in a layer that holds all
    its experts, never in a decode step (128 | 160 pairs round to one
    tile: more than half).  Where 512 divides the count it takes one tile
    more: the compiler's kernel tiles the rows by the largest of 512, 256
    and 128 that divides their count, and multiplies a whole tile for
    every group that has a row in it, so with a share's small groups
    (the balanced 85 rows an expert at Kimi's 4,096 bucket) 512-row
    tiles cost 3.77 ms a layer where 2,176 rows in tiles of 128 cost
    3.12, and 512 rows 3.48 where 640 cost 2.19 (v5e, PERF.md PR 43)."""
    balanced = -(-2 * pairs * held // num_experts)
    capacity = -(-balanced // ROW_TILE) * ROW_TILE
    if held == num_experts or 2 * capacity > pairs:
        return None
    return capacity + ROW_TILE if capacity % 512 == 0 else capacity


def combine_blocks(tokens: int, k: int, capacity: int) -> int:
    """Blocks of tokens the compact path's combine runs in; 1: ONE
    placement matmul ``[tokens, capacity] x [capacity, d]``.  From the
    shapes alone, as ``compact_capacity``.

    A block is one tile of 128 tokens, whose rows lie in a window of at
    most ``128 x k`` rows once the head is sorted by token: blocks
    multiply ``tokens x 128 x k x d`` where one block multiplies ``tokens
    x capacity x d``, and pay a second sort, a gather of ``capacity``
    rows and a loop turn a block for it.  They are taken where one block
    would multiply at least four times as much AND the square ``tokens x
    capacity`` has 2^23 entries or more.  On a v5e, the combine alone, one
    block | blocks, ms (PERF.md PR 54): 16 of 128 experts, top-8, d 4,096
    (``capacity`` = 2 x tokens + 128) at 16,384 tokens 24.6 | 4.6, 8,192
    6.2 | 2.3, 4,096 1.60 | 0.91, 2,048 0.46 | 0.34 (the edge: 2^23.04
    entries), 1,024 0.23 | 0.23; where the capacity is only twice the
    window (Kimi's 2,176 rows for 4,096 tokens of 7,168: 0.75 | 0.62) or
    the square small (Granite's 1,024 x 5,248: 0.31 | 0.33) nothing is
    won, and the one matmul stays: those programs are what they were."""
    window = ROW_TILE * k
    if tokens % ROW_TILE or capacity < 4 * window \
            or tokens * capacity < 1 << 23:
        return 1
    return tokens // ROW_TILE


class MoEMLP(nn.Module):
    """Drop-in replacement for a transformer MLP block.  ``gated``:
    three matrices per expert (``w_gate``, ``w_up``, ``w_down``: SwiGLU
    with ``act`` = silu), else two (``w_in``, ``w_out``)."""

    d_model: int
    d_ff: int
    num_experts: int
    top_k: int = 2
    gated: bool = False
    norm_topk_prob: bool = True
    scoring: str = "softmax"            # or "sigmoid"
    select_bias: bool = False           # choose on score + ``expert_bias``
    norm_eps: float = 0.0               # in the renormalisation's sum
    routed_scaling_factor: float = 1.0  # on the (renormalised) weights
    act: Callable = nn.gelu
    dtype: Any = jnp.bfloat16
    first_expert: int = 0               # the share held here:
    held_experts: Optional[int] = None  # None: all ``num_experts``

    @nn.compact
    def __call__(self, x: jnp.ndarray,
                 valid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        """x [B, T, d]; ``valid`` [B, T] bool marks the real rows (None:
        all)."""
        b, t, d = x.shape
        n, k, s = self.num_experts, self.top_k, b * t
        e = n if self.held_experts is None else self.held_experts
        first = self.first_expert
        if not 0 <= first <= first + e <= n:
            raise ValueError(f"experts {first}..{first + e} of {n}")
        init = nn.initializers.normal(0.02)
        router = self.param("router", init, (d, n), jnp.float32)
        # Moved by the experts' load outside autograd where a model is
        # trained with it; here it is as init drew it: no gradient, and
        # ``train_step.make_optimizer`` leaves the leaf out of its decay.
        bias = jax.lax.stop_gradient(self.param(
            "expert_bias", nn.initializers.zeros, (n,), jnp.float32)) \
            if self.select_bias else None
        names = ("w_up", "w_down") if self.gated else ("w_in", "w_out")
        w_gate = self.param("w_gate", init, (e, d, self.d_ff),
                            jnp.float32) if self.gated else None
        w_in = self.param(names[0], init, (e, d, self.d_ff), jnp.float32)
        w_out = self.param(names[1], init, (e, self.d_ff, d), jnp.float32)
        xf = x.reshape(s, d)
        real = jnp.ones((s,), bool) if valid is None else valid.reshape(s)

        with jax.named_scope("moe.route"):
            logits = xf.astype(jnp.float32) @ router.astype(jnp.float32)
            weights, experts, probs = route(
                logits, k, self.norm_topk_prob, self.scoring, bias,
                self.norm_eps)
            if self.routed_scaling_factor != 1.0:
                weights = weights * self.routed_scaling_factor
            # An invalid row's pairs go to "expert E": behind every
            # group, in none of them; so do the pairs of an expert that
            # is not held here.
            if e != n:
                experts = experts - first
                experts = jnp.where((experts >= 0) & (experts < e),
                                    experts, e)
                probs = probs[:, first:first + e]
            experts = jnp.where(real[:, None], experts, e)
            load = jnp.zeros((e + 1,), jnp.int32).at[
                experts.reshape(-1)].add(1)[:e]
            n_real = jnp.maximum(jnp.sum(real), 1).astype(jnp.float32)
            keep = real[:, None].astype(jnp.float32)
            capacity = compact_capacity(s * k, e, n)
            fits = jnp.zeros((), bool) if capacity is None \
                else jnp.sum(load) <= capacity
            self.sow("intermediates", "moe", {
                "load": load, "compact": fits,
                "prob_mean": jnp.sum(probs * keep, axis=0) / n_real,
                "z": jnp.sum(jnp.square(jax.nn.logsumexp(logits, axis=-1))
                             * keep[:, 0]) / n_real})

        dtype = self.dtype

        def experts_of(rows):
            with jax.named_scope("moe.experts"):
                return grouped_ffn(
                    rows, load,
                    None if w_gate is None else w_gate.astype(dtype),
                    w_in.astype(dtype), w_out.astype(dtype), self.act)

        def plain(order):
            """Every pair's row, in the sorted order: [S*k, .] arrays."""
            with jax.named_scope("moe.dispatch"):
                rows = xf.astype(dtype)[order // k]             # [S*k, d]
            out = experts_of(rows)
            with jax.named_scope("moe.combine"):
                # Rows behind the last group are whatever the kernel left
                # there: zeroed, not weighted.
                in_group = jnp.arange(s * k) < jnp.sum(load)
                out = jnp.where(in_group[:, None], out, 0)
                back = jnp.zeros((s * k,), jnp.int32).at[order].set(
                    jnp.arange(s * k, dtype=jnp.int32))
                y = jnp.einsum("skd,sk->sd", out[back].reshape(s, k, d),
                               weights.astype(dtype),
                               preferred_element_type=jnp.float32)
            return y.astype(dtype)

        def compact(order):
            """The held pairs alone: the sort is stable and every other
            pair carries the largest key, so they are the first
            ``sum(load)`` entries of ``order``, and ``capacity`` rows
            hold them all.  [capacity, .] arrays, no [S*k, d] one."""
            with jax.named_scope("moe.dispatch"):
                head = order[:capacity]
                token = head // k
                rows = xf.astype(dtype)[token]              # [capacity, d]
            out = experts_of(rows)
            with jax.named_scope("moe.combine"):
                in_group = jnp.arange(capacity) < jnp.sum(load)
                # By token, as a matmul with a row's weight at its token
                # (a scatter-add of the rows runs at 1.5 us a row on a
                # v5e: PERF.md, PR 43).
                blocks = combine_blocks(s, k, capacity)
                if blocks == 1:
                    # [S, capacity] x [capacity, d]: 0.13 TFLOP at Kimi's
                    # 4,096 bucket (4.4 at Command A+'s 16,384: blocks).
                    out = jnp.where(in_group[:, None], out, 0)
                    weight = weights.astype(dtype).reshape(-1)[head]
                    place = jnp.where(
                        jnp.arange(s)[:, None] == token[None, :],
                        weight[None, :], 0)
                    y = jnp.dot(place, out,
                                preferred_element_type=jnp.float32)
                    return y.astype(dtype)
                # By blocks of ``bt`` tokens.  Sorted by pair (token x k
                # + slot; a row behind the last group keeps the largest
                # key and token ``S``), a block's rows lie in one run of
                # at most ``bt x k`` rows that starts at the count of the
                # rows before it, and the block's matmul is over a window
                # of that many rows which covers the run.  Which rows of
                # a window count is still decided by comparing tokens, so
                # a window that ``dynamic_slice`` moves back from the end
                # of the rows gives the same sum.  What lies behind the
                # last group is zeroed in the window (zeroed before the
                # sort it is one more [capacity, d] array: 155 MB more of
                # ``prefill[16384]`` by the compiler's count).
                bt = s // blocks
                pair = jnp.where(in_group, head, s * k)
                by_token = jnp.argsort(pair, stable=True)
                token = (pair // k)[by_token]
                out = out[by_token]
                weight = weights.astype(dtype).reshape(-1)[head[by_token]]
                edges = jnp.arange(blocks, dtype=jnp.int32) * bt
                starts = jnp.sum(token[None, :] < edges[:, None], axis=1,
                                 dtype=jnp.int32)

                def block(_, at):
                    low, start = at
                    window, tok, w = (
                        jax.lax.dynamic_slice_in_dim(a, start, bt * k)
                        for a in (out, token, weight))
                    window = jnp.where((tok < s)[:, None], window, 0)
                    place = jnp.where(
                        (low + jnp.arange(bt))[:, None] == tok[None, :],
                        w[None, :], 0)
                    return None, jnp.dot(
                        place, window, preferred_element_type=jnp.float32
                    ).astype(dtype)

                _, y = jax.lax.scan(block, None, (edges, starts))
            return y.reshape(s, d)

        with jax.named_scope("moe.dispatch"):
            order = jnp.argsort(experts.reshape(-1), stable=True)
        y = plain(order) if capacity is None \
            else jax.lax.cond(fits, compact, plain, order)
        return y.reshape(b, t, d)


def moe_layers(intermediates) -> List[Dict[str, jnp.ndarray]]:
    """What each MoE layer sowed, in the tree's (layer) order."""
    found: List[Dict[str, jnp.ndarray]] = []

    def walk(node) -> None:
        if isinstance(node, dict) or hasattr(node, "items"):
            for key, child in node.items():
                if key == "moe" and isinstance(child, tuple):
                    found.extend(child)
                else:
                    walk(child)
    walk(intermediates)
    return found


def moe_losses(intermediates) -> Dict[str, jnp.ndarray]:
    """Summed over the layers, as the HF implementation sums them:
    ``load_balancing`` = E x sum_e f_e P_e (f_e the share of the S x k
    assignments that went to expert e, P_e its mean router probability),
    ``router_z`` = mean squared log-sum-exp of the router logits; and
    ``max_load_over_mean``, the fullest expert's rows over the mean, the
    worst layer's."""
    lb = z = jnp.float32(0.0)
    worst = jnp.float32(0.0)
    for layer in moe_layers(intermediates):
        load = layer["load"].astype(jnp.float32)
        e = load.shape[0]
        pairs = jnp.maximum(jnp.sum(load), 1.0)
        lb = lb + e * jnp.sum(load / pairs * layer["prob_mean"])
        z = z + layer["z"]
        worst = jnp.maximum(worst, jnp.max(load) * e / pairs)
    return {"load_balancing": lb, "router_z": z,
            "max_load_over_mean": worst}


MOE_COUNTERS = ("pairs", "experts_hit", "max_load", "compact")


def moe_counters(intermediates) -> Optional[jnp.ndarray]:
    """[layers, 4] int32, per layer ``MOE_COUNTERS``: the (real row,
    expert) pairs, the experts with at least one row, the largest group,
    and 1 where the call took the compact branch: what the engine
    fetches beside the logits.  None for a model without experts."""
    layers = moe_layers(intermediates)
    if not layers:
        return None
    return jnp.stack([
        jnp.stack([jnp.sum(m["load"]), jnp.sum(m["load"] > 0),
                   jnp.max(m["load"]), m["compact"]])
        for m in layers]).astype(jnp.int32)
