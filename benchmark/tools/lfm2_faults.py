#!/usr/bin/env python3
"""Shows that the comparison which decides ``correct`` for an LFM2 cell CAN
fail (as granite_faults.py does for Granite): the program as it is, and the
program with one thing wrong at a time, each served greedily through
``jit_forward`` and both caches (prefill padded to its bucket, then decode
steps in a padded batch) and held to the float32 reference as
``benchmark/harness/check.py`` holds the cell: at every generated position,
how far the served token's reference logit lies under the largest.

    python3 benchmark/tools/lfm2_faults.py [--layers N] [--seed S]
        [--prompt 160] [--tokens 48] [--rows 2] [--faults a,b]
        [--out chiprun_out/lfm2_faults.json]

With ``--rows 16 --prompt 960`` the decode batch holds the cell's 16 live
rows near its 1024 positions (prompts of 960, 955, ... tokens), where a
fault of the positions (RoPE's theta) has the most to show; ``--faults``
names the faults to read there (the reference's forward over sixteen
thousand positions takes minutes).

The program runs on the default backend (the chip, under chiprun), the
reference on the CPU backend of the same process.  The faults (FAULTS):
the experts chosen without ``expert_bias``; the bias inside the weights;
softmax scores for sigmoid; no renormalisation over the chosen; the gates
``B`` and ``C`` exchanged; two taps for three (the oldest dropped); the
window's two rows in the wrong order at a decode step; no per-head norm on
q and k; RoPE at theta 1e4; the dense layers' FFN left out; every matrix
rounded to 8 bits (float8 e4m3: the nearest precision below the one the
configuration states).  (RoPE BEFORE the per-head norm is no fault that can
show: a rotation keeps a head's mean square, and with the norms' scales at
1, as init draws them, ``norm(rope(q)) == rope(norm(q))``.)
Prints one JSON object: the gap of each against the traffic file's
tolerance."""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:] = [ROOT] + [p for p in sys.path if p != ROOT]

from benchmark.tools import granite_faults  # noqa: E402
from benchmark.tools.granite_faults import gaps  # noqa: E402,F401

FAULTS = ("no_select_bias", "bias_in_weights", "softmax_scores",
          "no_renorm", "gates_exchanged", "two_taps", "window_reversed",
          "no_qk_norm", "rope_theta_1e4", "no_dense_ffn", "weights_8bit")


def _edit(params, leaf, fn):
    """``params`` with ``fn`` applied to every leaf named ``leaf`` (given
    the leaf's path as a string)."""
    import jax

    def one(path, w):
        keys = [str(getattr(p, "key", p)) for p in path]
        return fn("/".join(keys), w) if keys[-1] == leaf else w

    return jax.tree_util.tree_map_with_path(one, params)


@contextlib.contextmanager
def fault(name, cfg, params, donate=False):
    """Yields (cfg, params) with ``name`` wrong (None: nothing wrong).
    ``donate``: a fault that rewrites every matrix takes the caller's
    buffers for it (at the published sizes the chip cannot hold the tree
    twice)."""
    import jax
    import jax.numpy as jnp

    import ray_tpu.models.lfm2 as lfm2
    import ray_tpu.ops.moe as moe

    if name == "weights_8bit":      # the nearest precision below bf16:
        with granite_faults.fault(  # the rounding is of the tree alone
                name, cfg, params, donate) as (cfg, params, _):
            yield cfg, params
        return
    undo = []

    def patch(module, attr, new):
        undo.append((module, attr, getattr(module, attr)))
        setattr(module, attr, new)

    real_route = moe.route
    if name == "no_select_bias":
        params = _edit(params, "expert_bias",
                       lambda _, w: jnp.zeros_like(w))
    elif name == "bias_in_weights":
        def biased(logits, k, norm, scoring, bias, eps):
            scores = jax.nn.sigmoid(logits) + bias
            w, e = jax.lax.top_k(scores, k)
            return w / (jnp.sum(w, -1, keepdims=True) + eps), e, scores
        patch(moe, "route", biased)
    elif name == "softmax_scores":
        patch(moe, "route", lambda logits, k, norm, scoring, bias, eps:
              real_route(logits, k, norm, "softmax", bias, eps))
    elif name == "no_renorm":
        patch(moe, "route", lambda logits, k, norm, scoring, bias, eps:
              real_route(logits, k, False, scoring, bias, eps))
    elif name == "gates_exchanged":
        def swap(path, w):      # in_proj's columns [B | C | X] -> [C | B | X]
            if "/in_proj/" not in path:
                return w
            b, c, x = jnp.split(w, 3, axis=-1)
            return jnp.concatenate([c, b, x], axis=-1)
        params = _edit(params, "kernel", swap)
    elif name == "two_taps":
        params = _edit(params, "conv_w", lambda _, w: w.at[0].set(0))
    elif name == "window_reversed":
        real_conv = lfm2.slot_conv

        def reversed_at_decode(x, taps, window=None, **kw):
            if window is not None and x.shape[1] == 1:
                window = (window[0][:, :, ::-1],) + tuple(window[1:])
                out, pool = real_conv(x, taps, window, **kw)
                return out, pool[:, :, ::-1]
            return real_conv(x, taps, window, **kw)
        patch(lfm2, "slot_conv", reversed_at_decode)
    elif name == "no_qk_norm":
        class Skipped(lfm2.RMSNorm):
            def __call__(self, x):
                if self.name in ("q_norm", "k_norm"):
                    return x
                return super().__call__(x)
        patch(lfm2, "RMSNorm", Skipped)
    elif name == "rope_theta_1e4":
        cfg = dataclasses.replace(cfg, rope_theta=10000.0)
    elif name == "no_dense_ffn":
        params = _edit(params, "kernel", lambda path, w: jnp.zeros_like(w)
                       if path.endswith("/w_down/kernel") else w)
    elif name is not None:
        raise ValueError(name)
    try:
        yield cfg, params
    finally:
        for module, attr, old in reversed(undo):
            setattr(module, attr, old)


def serve(cfg, params, prompts, n_tokens, max_batch=None, page=16,
          forced=None):
    """Greedy tokens and their logits for ``prompts`` through the
    engine's jitted forward: each prompt prefilled ([1, bucket], padded),
    then all decoded together in a [max_batch, 1] batch with row 1 left
    EMPTY (a hole), each sequence in the slot of its row; the state pool
    starts from other numbers than zeros.  With ``forced`` (tokens per
    prompt) those are fed instead of the argmax (teacher forcing), and
    the argmax is still what is returned."""
    import numpy as np

    from ray_tpu.llm.engine import _bucket, jit_forward
    from ray_tpu.llm.kv_cache import init_cache, init_state, pages_for
    from ray_tpu.models import family_of

    fam = family_of(cfg)
    spec = fam.cache(cfg)
    rows = [0] + list(range(2, len(prompts) + 1))       # row 1: the hole
    max_batch = max_batch or len(prompts) + 2
    longest = max(len(p) for p in prompts) + n_tokens
    per_seq = pages_for(longest, page)
    kv = init_cache(spec.kv_layers, per_seq * max_batch, page,
                    spec.kv_heads, spec.head_dim, cfg.dtype)
    # a slot that was used before: what it held must not matter
    state = [a + 1 for a in init_state(spec, max_batch, cfg.dtype).values()]
    k, v = kv["k_pages"], kv["v_pages"]
    fwd = jit_forward(fam.module(cfg))
    table = np.zeros((max_batch, per_seq), np.int32)
    seqs = [list(p) for p in prompts]
    served = [[] for _ in prompts]
    logits_out = [[] for _ in prompts]

    def take(i, row_logits, step):
        logits_out[i].append(np.asarray(row_logits, np.float32))
        tok = int(np.argmax(logits_out[i][-1]))
        served[i].append(tok)
        seqs[i].append(tok if forced is None else forced[i][step])

    def run(toks, table_rows, pos, slots):
        nonlocal k, v, state
        logits, k, v, *rest = fwd(params, toks, k, v, table_rows, pos,
                                  *state, slots)
        state = rest[:len(state)]
        return logits

    for i, prompt in enumerate(prompts):
        row = rows[i]
        table[row] = np.arange(per_seq) + row * per_seq
        n, pad = len(prompt), _bucket(len(prompt))
        toks = np.zeros((1, pad), np.int32)
        toks[0, :n] = prompt
        pos = np.full((1, pad), -1, np.int32)
        pos[0, :n] = np.arange(n)
        logits = run(toks, table[row:row + 1], pos,
                     np.array([row], np.int32))
        take(i, logits[0, n - 1], 0)
    for step in range(1, n_tokens):
        toks = np.zeros((max_batch, 1), np.int32)
        pos = np.full((max_batch, 1), -1, np.int32)
        slots = np.full((max_batch,), max_batch, np.int32)
        for i, s in enumerate(seqs):
            toks[rows[i], 0], pos[rows[i], 0] = s[-1], len(s) - 1
            slots[rows[i]] = rows[i]
        logits = np.asarray(run(toks, table, pos, slots))
        for i in range(len(seqs)):
            take(i, logits[rows[i], 0], step)
    return served, logits_out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="lfm2-24b-a2b")
    ap.add_argument("--traffic", default="offline-closed-768")
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--prompt", type=int, default=160)
    ap.add_argument("--tokens", type=int, default=48)
    ap.add_argument("--rows", type=int, default=2,
                    help="live rows of the decode batch (2: prompts of "
                    "--prompt and about half of it; more: --prompt less "
                    "5 a row)")
    ap.add_argument("--faults", default=",".join(FAULTS),
                    help="which faults, comma-separated")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "lfm2_faults.json"))
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import manifest
    from benchmark.harness.families import family_of
    from benchmark.reference import lfm2_moe_ref as ref

    config = manifest.load_json(os.path.join(
        ROOT, "benchmark", "configs", args.config + ".json"), "config")
    check = manifest.load_json(os.path.join(
        ROOT, "benchmark", "traffic", args.traffic + ".json"),
        "traffic")["check"]
    if args.layers:
        config["num_hidden_layers"] = args.layers
    fam = family_of(config)
    cfg = fam.program_config(config, attn_impl="dense", remat=False)
    params = fam.init(cfg, jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng([args.seed, 0x6661])
    lengths = (args.prompt, args.prompt // 2 + 7) if args.rows == 2 \
        else [args.prompt - 5 * i for i in range(args.rows)]
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in lengths]
    faults = [f for f in FAULTS if f in args.faults.split(",")]
    assert len(faults) == len(args.faults.split(",")), args.faults
    served, _ = serve(cfg, params, prompts, args.tokens)
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        on_cpu = jax.device_put(params, cpu)
        ref_logits = [np.asarray(ref.forward(
            config, on_cpu, jnp.asarray([prompt + toks[:-1]], jnp.int32)))[0]
            for prompt, toks in zip(prompts, served)]
        del on_cpu
    own = [float(np.mean(np.array(s) == np.array((p + s)[len(p) - 1:-1])))
           for p, s in zip(prompts, served)]
    out = {"backend": jax.default_backend(), "seed": args.seed,
           "layers": config["num_hidden_layers"],
           "tolerance": check["logit_tolerance"],
           "positions": [len(p) + args.tokens for p in prompts],
           "logit_std": float(np.std(ref_logits[0])),
           # the share of served tokens that repeat their own input token
           # (the tied head's pull: Granite's lesson)
           "repeats_input_share": own,
           "gap": {"as_it_is": max(
               gaps(r, len(pr), s)
               for r, pr, s in zip(ref_logits, prompts, served))}}
    # Each fault is fed the right program's tokens (one reference
    # forward serves all) and judged by the tokens IT would have served.
    assert FAULTS[-1] == "weights_8bit"     # it takes the tree: last
    for name in faults:
        with fault(name, cfg, params, donate=True) as (c, p):
            would, _ = serve(c, p, prompts, args.tokens, forced=served)
        out["gap"][name] = max(gaps(r, len(pr), s) for r, pr, s
                               in zip(ref_logits, prompts, would))
    out["fails"] = {k: not v <= out["tolerance"]
                    for k, v in out["gap"].items()}
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
