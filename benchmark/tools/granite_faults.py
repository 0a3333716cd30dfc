#!/usr/bin/env python3
"""Shows that the comparison which decides ``correct`` for a Granite cell
CAN fail: the program as it is, and the program with one thing wrong at a
time, each served greedily through ``jit_forward`` and both caches
(prefill padded to its bucket, then decode steps in a padded batch) and
held to the float32 reference as ``benchmark/harness/check.py`` holds the
cell: at every generated position, how far the served token's reference
logit lies under the largest.

    python3 benchmark/tools/granite_faults.py [--layers N] [--seed S]
        [--prompt 160] [--tokens 48] [--out chiprun_out/granite_faults.json]

The program runs on the default backend (the chip, under chiprun), the
reference on the CPU backend of the same process.  The faults: the
recurrent state held in bf16; no ``D x`` term; no conv bias; attention
scaled by 1/sqrt(head size) instead of ``attention_multiplier``; rotary
position encoding on q and k; every matrix rounded to 8 bits (float8
e4m3: the nearest precision below the one the configuration states).
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

FAULTS = ("bf16_state", "no_D", "no_conv_bias", "sqrt_scale", "rotary",
          "weights_8bit")


@contextlib.contextmanager
def fault(name, cfg, params, donate=False):
    """Yields (cfg, params, dtype of the state pool's ``ssm``) with
    ``name`` wrong (None: nothing wrong).  ``donate``: a fault that
    rewrites every matrix takes the caller's buffers for it (at the
    published sizes the chip cannot hold the tree twice)."""
    import jax
    import jax.numpy as jnp

    import ray_tpu.models.granite as granite

    def without(leaf):
        return jax.tree_util.tree_map_with_path(
            lambda path, w: jnp.zeros_like(w)
            if str(getattr(path[-1], "key", "")) == leaf else w, params)

    ssm_dtype, undo = jnp.float32, None
    if name == "bf16_state":
        ssm_dtype = jnp.bfloat16
    elif name == "no_D":
        params = without("D")
    elif name == "no_conv_bias":
        params = without("conv_b")
    elif name == "sqrt_scale":
        cfg = dataclasses.replace(
            cfg, attention_multiplier=cfg.head_dim ** -0.5)
    elif name == "rotary":
        from ray_tpu.models.llama import _rope

        real = granite.attention

        def rotated(cfg_, q, k, v, cache=None, scale=None):
            pos = None if cache is None else cache["positions"]
            return real(cfg_, _rope(q, 10000.0, pos), _rope(k, 10000.0, pos),
                        v, cache, scale=scale)

        granite.attention, undo = rotated, real
    elif name == "weights_8bit":    # the nearest precision below bf16
        # (reduce_precision: a cast to float8 and back is a round trip
        # the TPU's compiler may drop as excess precision, and did)
        round8 = jax.jit(
            lambda w: jax.lax.reduce_precision(
                w.astype(jnp.float32), exponent_bits=4,
                mantissa_bits=3).astype(w.dtype),
            donate_argnums=(0,) if donate else ())
        params = jax.tree_util.tree_map(
            lambda w: round8(w) if w.ndim > 1 else w, params)
    elif name is not None:
        raise ValueError(name)
    try:
        yield cfg, params, ssm_dtype
    finally:
        if undo is not None:
            granite.attention = undo


def serve(cfg, params, prompts, n_tokens, ssm_dtype, max_batch=None,
          page=16, forced=None):
    """Greedy tokens and their logits for ``prompts`` through the
    engine's jitted forward: each prompt prefilled ([1, bucket], padded),
    then all decoded together in a [max_batch, 1] batch with row 1 left
    EMPTY (a hole), each sequence in the slot of its row.  With
    ``forced`` (tokens per prompt) those are fed instead of the argmax
    (teacher forcing), and the argmax is still what is returned."""
    import jax.numpy as jnp
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
    state = init_state(spec, max_batch, cfg.dtype)
    state["ssm"] = state["ssm"].astype(ssm_dtype)
    # a slot that was used before: what it held must not matter
    state = {k: v + 1 for k, v in state.items()}
    k, v, conv, ssm = kv["k_pages"], kv["v_pages"], state["conv"], \
        state["ssm"]
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

    for i, prompt in enumerate(prompts):
        row = rows[i]
        table[row] = np.arange(per_seq) + row * per_seq
        n, pad = len(prompt), _bucket(len(prompt))
        toks = np.zeros((1, pad), np.int32)
        toks[0, :n] = prompt
        pos = np.full((1, pad), -1, np.int32)
        pos[0, :n] = np.arange(n)
        logits, k, v, conv, ssm, *_ = fwd(
            params, toks, k, v, table[row:row + 1], pos, conv, ssm,
            np.array([row], np.int32))
        take(i, logits[0, n - 1], 0)
    for step in range(1, n_tokens):
        toks = np.zeros((max_batch, 1), np.int32)
        pos = np.full((max_batch, 1), -1, np.int32)
        slots = np.full((max_batch,), max_batch, np.int32)
        for i, s in enumerate(seqs):
            toks[rows[i], 0], pos[rows[i], 0] = s[-1], len(s) - 1
            slots[rows[i]] = rows[i]
        logits, k, v, conv, ssm, *_ = fwd(params, toks, k, v, table, pos,
                                          conv, ssm, slots)
        logits = np.asarray(logits)
        for i in range(len(seqs)):
            take(i, logits[rows[i], 0], step)
    return served, logits_out


def gaps(ref_logits, prompt_len, served):
    """check.py's measure: over the generated positions, the largest
    reference logit less the served token's."""
    import numpy as np

    first = prompt_len - 1
    rows = ref_logits[first:first + len(served)]
    return float(np.max(rows.max(axis=-1)
                        - rows[np.arange(len(served)), served]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="granite-4.0-h-small")
    ap.add_argument("--traffic", default="offline-closed-384")
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--prompt", type=int, default=160)
    ap.add_argument("--tokens", type=int, default=48)
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "granite_faults.json"))
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import manifest
    from benchmark.harness.families import family_of
    from benchmark.reference import granitemoehybrid_ref as ref

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
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (args.prompt, args.prompt // 2 + 7)]
    with fault(None, cfg, params) as (c, p, dt):
        served, _ = serve(c, p, prompts, args.tokens, dt)
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        on_cpu = jax.device_put(params, cpu)
        ref_logits = [np.asarray(ref.forward(
            config, on_cpu, jnp.asarray([prompt + toks[:-1]], jnp.int32)))[0]
            for prompt, toks in zip(prompts, served)]
    out = {"backend": jax.default_backend(), "seed": args.seed,
           "layers": config["num_hidden_layers"],
           "tolerance": check["logit_tolerance"],
           "positions": [len(p) + args.tokens for p in prompts],
           "gap": {"as_it_is": max(
               gaps(r, len(pr), s)
               for r, pr, s in zip(ref_logits, prompts, served))}}
    # Each fault is fed the right program's tokens (one reference
    # forward serves all) and judged by the tokens IT would have served.
    assert FAULTS[-1] == "weights_8bit"     # it takes the tree: last
    for name in FAULTS:
        with fault(name, cfg, params, donate=True) as (c, p, dt):
            would, _ = serve(c, p, prompts, args.tokens, dt, forced=served)
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
