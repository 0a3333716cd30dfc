#!/usr/bin/env python3
"""Shows that the comparison which decides ``correct`` for a Kimi-K2 cell
CAN fail (as lfm2_faults.py does for LFM2), at the TIMED sizes, which the
CPU child of a run cannot hold: the program as it is, and the program with
one thing wrong at a time, each served greedily through ``jit_forward`` and
the latent pool (every prompt prefilled padded to its bucket, the EXPANDED
path; then decode steps in a padded batch, the ABSORBED path over the
pages) and held to the float32 reference as ``benchmark/harness/check.py``
holds the cell: at every generated position, how far the served token's
reference logit lies under the largest.

    python3 benchmark/tools/kimi_faults.py [--layers N] [--seed S]
        [--rows 16] [--prompt 3600] [--spread 40] [--tokens 48]
        [--ref-rows 16] [--faults a,b] [--out chiprun_out/kimi_faults.json]

The default is the cell's shape: 16 live rows at 3,000-3,648 positions
(prompts of 3600, 3560, ... tokens, each through the 4,096 bucket), of
which one crosses a block boundary of the decode kernel (3,584 = 7 x 512)
while it decodes.  Program AND reference run on the default backend (the
chip, under chiprun): the reference in float32 at
``jax.default_matmul_precision("highest")``, its attention in blocks of
positions, a layer's matrices read as float32 at a time.

The faults (FAULTS): the selection bias left out; ``routed_scaling_factor``
left out; the shared expert left out; ``k_pe`` left out of the scores;
``W_kva``'s output split as ``k_pe | c`` (the rope on the wrong 64
dimensions); ``c_kv`` used and stored un-normed; ``mscale ** 2`` left out
of the scale; YaRN's interpolation left out (plain RoPE at theta); the
decode step's pages read one block short when a length is just past a
block boundary; every matrix rounded to 8 bits (float8 e4m3: the nearest
precision below the one the configuration states).
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
from benchmark.tools.lfm2_faults import _edit  # noqa: E402

FAULTS = ("no_select_bias", "no_routed_scaling", "no_shared_expert",
          "no_k_pe", "rope_wrong_dims", "ckv_unnormed", "no_mscale",
          "no_yarn", "pages_one_block_short", "weights_8bit")
# "Just past" a block boundary: this many positions or fewer into a block.
JUST_PAST = 8


def _short(lengths, block_rows):
    """``lengths`` with the last, barely begun block of positions left
    unread."""
    import jax.numpy as jnp

    into = lengths % block_rows
    return jnp.where((into > 0) & (into <= JUST_PAST) & (lengths > into),
                     lengths - into, lengths)


@contextlib.contextmanager
def fault(name, cfg, params, donate=False, block_rows=None):
    """Yields (cfg, params) with ``name`` wrong (None: nothing wrong).
    ``donate``: a fault that rewrites every matrix takes the caller's
    buffers for it (at the published sizes the chip cannot hold the tree
    twice).  ``block_rows``: positions a block of the decode attention
    holds, for ``pages_one_block_short`` (None: the kernel's own)."""
    import jax.numpy as jnp

    import ray_tpu.llm.kv_cache as kv_cache
    import ray_tpu.models.kimi as kimi
    from ray_tpu.ops import paged_attention

    if name == "weights_8bit":      # the nearest precision below bf16:
        with granite_faults.fault(  # the rounding is of the tree alone
                name, cfg, params, donate) as (cfg, params, _):
            yield cfg, params
        return
    undo = []

    def patch(module, attr, new):
        undo.append((module, attr, getattr(module, attr)))
        setattr(module, attr, new)

    if name == "no_select_bias":
        params = _edit(params, "expert_bias",
                       lambda _, w: jnp.zeros_like(w))
    elif name == "no_routed_scaling":
        cfg = dataclasses.replace(cfg, routed_scaling_factor=1.0)
    elif name == "no_shared_expert":
        params = _edit(params, "kernel", lambda path, w: jnp.zeros_like(w)
                       if path.endswith("/shared_down/kernel") else w)
    elif name == "no_k_pe":
        real = kimi.latent_attention
        patch(kimi, "latent_attention",
              lambda cfg, q_nope, q_pe, *rest: real(
                  cfg, q_nope, jnp.zeros_like(q_pe), *rest))
    elif name == "rope_wrong_dims":
        d_r = cfg.qk_rope_head_dim
        params = _edit(params, "kernel", lambda path, w: jnp.roll(
            w, d_r, axis=-1) if path.endswith("/wkv_a/kernel") else w)
    elif name == "ckv_unnormed":
        class Skipped(kimi.RMSNorm):
            def __call__(self, x):
                if self.name == "kv_norm":
                    return x
                return super().__call__(x)
        patch(kimi, "RMSNorm", Skipped)
    elif name == "no_mscale":
        cfg = dataclasses.replace(cfg, rope_mscale=0.0,
                                  rope_mscale_all_dim=0.0)
    elif name == "no_yarn":
        real_rope = kimi._rope
        patch(kimi, "_rope", lambda x, theta, positions=None, yarn=None:
              real_rope(x, theta, positions))
    elif name == "pages_one_block_short":
        kernel, plain = (paged_attention.paged_decode_latent,
                         kv_cache.latent_attend)

        def short_kernel(q_lat, q_pe, pages, layer, table, lengths, **kw):
            rows = block_rows or pages.shape[2] * kw.get(
                "block_pages", paged_attention.LATENT_BLOCK_PAGES)
            return kernel(q_lat, q_pe, pages, layer, table,
                          _short(lengths, rows), **kw)

        def short_plain(q_lat, q_pe, pages, layer, table, positions,
                        scale):
            rows = block_rows or pages.shape[2] \
                * paged_attention.LATENT_BLOCK_PAGES
            return plain(q_lat, q_pe, pages, layer, table,
                         _short(positions + 1, rows) - 1, scale)

        patch(paged_attention, "paged_decode_latent", short_kernel)
        patch(kv_cache, "latent_attend", short_plain)
    elif name is not None:
        raise ValueError(name)
    try:
        yield cfg, params
    finally:
        for module, attr, old in reversed(undo):
            setattr(module, attr, old)


def serve(cfg, params, prompts, n_tokens, max_batch=None, page=16,
          forced=None, hole=True):
    """Greedy tokens and their logits for ``prompts`` through the
    engine's jitted forward: each prompt prefilled ([1, bucket], padded),
    then all decoded together in a [max_batch, 1] batch with row 1 left
    EMPTY (a hole; ``hole=False``: every row live, the cell's full batch);
    the pool starts from other numbers than zeros in the
    rows' lanes (a page that changed hands).  With ``forced`` (tokens per
    prompt) those are fed instead of the argmax (teacher forcing), and
    the argmax is still what is returned."""
    import numpy as np

    from ray_tpu.llm.engine import _bucket, jit_forward
    from ray_tpu.llm.kv_cache import init_pool, pages_for
    from ray_tpu.models import family_of

    fam = family_of(cfg)
    spec = fam.cache(cfg)
    rows = [0] + list(range(2, len(prompts) + 1)) if hole \
        else list(range(len(prompts)))                  # row 1: the hole
    max_batch = max_batch or len(prompts) + 2
    longest = max(len(p) for p in prompts) + n_tokens
    per_seq = pages_for(longest, page)
    # pages that were used before: what they held must not matter
    (pages,) = (a + 1 for a in init_pool(spec, per_seq * max_batch, page,
                                         cfg.dtype).values())
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

    def run(toks, table_rows, pos):
        nonlocal pages
        logits, pages, *_ = fwd(params, toks, pages, table_rows, pos)
        return logits

    for i, prompt in enumerate(prompts):
        row = rows[i]
        table[row] = np.arange(per_seq) + row * per_seq
        n, pad = len(prompt), _bucket(len(prompt))
        toks = np.zeros((1, pad), np.int32)
        toks[0, :n] = prompt
        pos = np.full((1, pad), -1, np.int32)
        pos[0, :n] = np.arange(n)
        logits = run(toks, table[row:row + 1], pos)
        take(i, logits[0, n - 1], 0)
    for step in range(1, n_tokens):
        toks = np.zeros((max_batch, 1), np.int32)
        pos = np.full((max_batch, 1), -1, np.int32)
        for i, s in enumerate(seqs):
            toks[rows[i], 0], pos[rows[i], 0] = s[-1], len(s) - 1
        logits = np.asarray(run(toks, table, pos))
        for i in range(len(seqs)):
            take(i, logits[rows[i], 0], step)
    return served, logits_out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="kimi-k2.5")
    ap.add_argument("--traffic", default="offline-closed-4k")
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--rows", type=int, default=16,
                    help="live rows of the decode batch")
    ap.add_argument("--prompt", type=int, default=3600,
                    help="the longest prompt; row i has --spread x i fewer")
    ap.add_argument("--spread", type=int, default=40)
    ap.add_argument("--tokens", type=int, default=48)
    ap.add_argument("--ref-rows", type=int, default=None,
                    help="rows held to the reference (the first ones; "
                    "None: all)")
    ap.add_argument("--faults", default=",".join(FAULTS),
                    help="which faults, comma-separated ('' for none)")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "kimi_faults.json"))
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import manifest
    from benchmark.harness.families import family_of
    from benchmark.reference import kimi_k2_ref as ref

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
    lengths = [args.prompt - args.spread * i for i in range(args.rows)]
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in lengths]
    wanted = [f for f in args.faults.split(",") if f]
    faults = [f for f in FAULTS if f in wanted]
    assert len(faults) == len(wanted), args.faults
    # the cell's own decode shape: 16 rows, all live where --rows is 16
    shape = dict(max_batch=max(args.rows, 16), hole=args.rows < 16)
    served, _ = serve(cfg, params, prompts, args.tokens, **shape)
    held = range(min(args.ref_rows or args.rows, args.rows))
    # the reference's logits at the generated positions only
    ref_rows = []
    for i in held:
        full = ref.forward(config, params, jnp.asarray(
            [prompts[i] + served[i][:-1]], jnp.int32))[0]
        ref_rows.append(np.asarray(full[lengths[i] - 1:]))
        del full

    def worst(tokens):
        return max(gaps(ref_rows[i], 1, tokens[i]) for i in held)

    agree = float(np.mean([np.mean(ref_rows[i].argmax(-1)
                                   == np.array(served[i])) for i in held]))
    out = {"backend": jax.default_backend(), "seed": args.seed,
           "layers": config["num_hidden_layers"],
           "tolerance": check["logit_tolerance"],
           "positions": [n + args.tokens for n in lengths],
           "rows_held_to_the_reference": len(held),
           "logit_std": float(np.std(ref_rows[0])),
           "argmax_agree": agree,
           "gap": {"as_it_is": worst(served)}}
    print(json.dumps(out), flush=True)
    # Each fault is fed the right program's tokens (one reference
    # forward serves all) and judged by the tokens IT would have served.
    assert FAULTS[-1] == "weights_8bit"     # it takes the tree: last
    for name in faults:
        with fault(name, cfg, params, donate=True) as (c, p):
            would, _ = serve(c, p, prompts, args.tokens, forced=served,
                             **shape)
        out["gap"][name] = worst(would)
        print(json.dumps({name: out["gap"][name]}), flush=True)
    out["fails"] = {k: not v <= out["tolerance"]
                    for k, v in out["gap"].items()}
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
