#!/usr/bin/env python3
"""Holds the WINDOW of a Command A+ cell to the reference, which a run's
own check cannot (benchmark/run.py picks among the requests that fit the
CPU child and cannot be told to take one past the window), and shows that
the comparison which decides ``correct`` CAN fail: at the TIMED sizes the
program as it is, and the program with one thing wrong at a time, each
served greedily through ``jit_forward`` and BOTH groups of the K/V pool
(every prompt prefilled padded to its bucket as the engine prefills,
``last=`` the prompt's last position: the flash kernel under the band in
the window layers and over the causal triangle in the full one, the last
``window`` rows stored into the ring; then decode steps in a batch: the
paged kernel over the rings and over the full layer's pages) and held to
the float32 reference as ``benchmark/harness/check.py`` holds the cell: at
every generated position, how far the served token's reference logit lies
under the largest.

    python3 benchmark/tools/command_a_faults.py [--seed S]
        [--lengths 16320,...] [--tokens 48] [--ref-rows 16]
        [--faults a,b] [--out chiprun_out/command_a_faults.json]

The default is the cell's shape: 16 live rows of 6,100-16,320 positions
(four through the 8,192 bucket, twelve through the 16,384 one: every row
longer than the window of 4,096, so every ring has wrapped), then 48 decode
steps in which two rows cross a multiple of 4,096 (8,170 -> 8,192 and
12,270 -> 12,288: the ring's write position passes its end).  Program AND
reference run on the default backend (the chip, under chiprun): the
reference in float32 at ``jax.default_matmul_precision("highest")``, a
layer a ``jit`` (``forward(by_layer=True)``), a row at a time, its attention
in blocks of positions, the head over the generated positions alone.  Every
reading is written to ``--out`` as it is made.

The faults (FAULTS): the band off by one (key ``i - window`` visible); a
window layer's prefill attending every earlier key; RoPE on the full layer;
rotate-half pairing in the window layers; RMSNorm for LayerNorm; a
sequential block (attention, then the experts on a second norm of the
result); the shared experts summed, not averaged; softmax for sigmoid
scores in the router; every matrix rounded to 8 bits (float8 e4m3: the
nearest precision below the one the configuration states).
Prints one JSON object: the gap of each against the traffic file's
tolerance."""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:] = [ROOT] + [p for p in sys.path if p != ROOT]

from benchmark.tools import granite_faults  # noqa: E402
from benchmark.tools.granite_faults import gaps  # noqa: E402,F401

FAULTS = ("band_off_by_one", "window_blind", "rope_on_full", "rotate_half",
          "rms_norm", "sequential_block", "shared_summed", "router_softmax",
          "weights_8bit")
# This one changes the tree alone: the program's text stays, and the
# jitted forward that served the right program serves it.
SAME_PROGRAM = ("weights_8bit",)
LENGTHS = (16320, 15600, 14900, 14200, 13500, 12800, 12270, 11400, 10700,
           10000, 9300, 8600, 8170, 7400, 6700, 6100)


@contextlib.contextmanager
def fault(name, cfg, params, donate=False):
    """Yields (cfg, params) with ``name`` wrong (None: nothing wrong).
    ``donate``: a fault that rewrites every matrix takes the caller's
    buffers for it (at the published sizes the chip cannot hold the tree
    twice)."""
    import ray_tpu.models.attention as attention
    import ray_tpu.models.cohere as cohere
    from ray_tpu.models.decoder import (Attention, attention_kind,
                                        window_kind)
    from ray_tpu.models.layers import RMSNorm

    if name == "weights_8bit":      # the nearest precision below bf16:
        with granite_faults.fault(  # the rounding is of the tree alone
                name, cfg, params, donate) as (cfg, params, _):
            yield cfg, params
        return
    undo = []

    def patch(owner, attr, new):
        undo.append((owner, attr, owner.__dict__[attr]
                     if isinstance(owner, type) else getattr(owner, attr)))
        setattr(owner, attr, new)

    def kinds(**changed):
        mixers = dict(cohere.MIXERS)
        mixers.update(changed)
        patch(cohere, "MIXERS", mixers)

    def core(edit, **flash):
        """The attention core's ``window`` through ``edit``."""
        real = attention._attention

        def edited(cfg_, q, k, v, scale=None, impl=None, window=None):
            return real(cfg_, q, k, v, scale, impl, edit(window))
        patch(attention, "_attention", edited)
        if flash:
            patch(attention, "_FLASH", {**attention._FLASH, **flash})

    if name == "band_off_by_one":   # key i - window seen too
        # (a window of whole blocks plus one is no window of whole blocks:
        # the kernel computes its edge blocks whole, in blocks of 512)
        core(lambda w: None if w is None else w + 1, block_q=512,
             block_k=512)
    elif name == "window_blind":    # a prefill's window layers see it all
        core(lambda w: None)
    elif name == "rope_on_full":
        kinds(**{cohere.FULL: attention_kind(functools.partial(
            Attention, interleaved=True, core_scope="attn.full"),
            norm="norm")})
    elif name == "rotate_half":     # dimension i with i + D/2
        kinds(**{cohere.SLIDING: window_kind(
            lambda cfg_, name: Attention(
                cfg_, window=cfg_.sliding_window, core_scope="attn.window",
                name=name), norm="norm")})
    elif name == "rms_norm":        # the mean left in
        patch(cohere.Cohere2MoeConfig, "norm", RMSNorm)
    elif name == "sequential_block":
        # attention, then the experts on a norm of the RESULT: the second
        # norm's scale is the first's
        patch(cohere.Cohere2MoeConfig, "parallel_block", False)
        params = {"params": {
            key: dict(layer, mlp_norm=layer["norm"])
            if key.startswith("layer_") else layer
            for key, layer in params["params"].items()}}
    elif name == "shared_summed":
        patch(cohere.Cohere2MoeConfig, "shared_multiplier", 1.0)
    elif name == "router_softmax":
        real = cohere.Cohere2MoeConfig.__dict__["experts"]
        patch(cohere.Cohere2MoeConfig, "experts", property(
            lambda self: dict(real.fget(self), scoring="softmax")))
    elif name is not None:
        raise ValueError(name)
    try:
        yield cfg, params
    finally:
        for owner, attr, old in reversed(undo):
            setattr(owner, attr, old)


def serve(cfg, params, prompts, n_tokens, max_batch=None, page=16,
          forced=None, hole=True, fwd=None):
    """Greedy tokens and their logits for ``prompts`` through the engine's
    jitted forward and both groups of the K/V pool: each prompt prefilled
    ([1, bucket], padded, ``last=`` its last position as the engine passes
    it) into the pages and the ring of its row, then all decoded together in
    a [max_batch, 1] batch with row 1 left EMPTY (a hole; ``hole=False``:
    every row live, the cell's full batch); pages start from other numbers
    than zeros (they changed hands).  With ``forced`` (tokens per prompt)
    those are fed instead of the argmax (teacher forcing), and the argmax
    is still what is returned.  ``fwd``: a jitted forward to use again."""
    import numpy as np

    from ray_tpu.llm.engine import _bucket, jit_forward
    from ray_tpu.llm.kv_cache import init_pool, pages_for, ring_pages
    from ray_tpu.models import family_of

    fam = family_of(cfg)
    spec = fam.cache(cfg)
    rows = [0] + list(range(2, len(prompts) + 1)) if hole \
        else list(range(len(prompts)))                  # row 1: the hole
    max_batch = max_batch or len(prompts) + 2
    longest = max(len(p) for p in prompts) + n_tokens
    per_seq, ring = pages_for(longest, page), ring_pages(spec, page)
    pools = [a + 1 for a in init_pool(
        spec, per_seq * max_batch, page, cfg.dtype,
        ring * max_batch).values()]
    fwd = fwd or jit_forward(fam.module(cfg))
    table = np.zeros((max_batch, per_seq), np.int32)
    rings = np.zeros((max_batch, ring), np.int32)
    seqs = [list(p) for p in prompts]
    served = [[] for _ in prompts]
    logits_out = [[] for _ in prompts]

    def take(i, row_logits, step):
        logits_out[i].append(np.asarray(row_logits, np.float32))
        tok = int(np.argmax(logits_out[i][-1]))
        served[i].append(tok)
        seqs[i].append(tok if forced is None else forced[i][step])

    def run(toks, tables, pos, **last):
        nonlocal pools
        logits, *rest = fwd(params, toks, *pools, *tables, pos, **last)
        pools = rest[:len(pools)]
        return logits

    for i, prompt in enumerate(prompts):
        row = rows[i]
        table[row] = np.arange(per_seq) + row * per_seq
        rings[row] = np.arange(ring) + row * ring
        n, pad = len(prompt), _bucket(len(prompt))
        toks = np.zeros((1, pad), np.int32)
        toks[0, :n] = prompt
        pos = np.full((1, pad), -1, np.int32)
        pos[0, :n] = np.arange(n)
        logits = run(toks, (table[row:row + 1], rings[row:row + 1]), pos,
                     last=np.array([n - 1], np.int32))
        take(i, logits[0, 0], 0)
    for step in range(1, n_tokens):
        toks = np.zeros((max_batch, 1), np.int32)
        pos = np.full((max_batch, 1), -1, np.int32)
        for i, s in enumerate(seqs):
            toks[rows[i], 0], pos[rows[i], 0] = s[-1], len(s) - 1
        logits = np.asarray(run(toks, (table, rings), pos))
        for i in range(len(seqs)):
            take(i, logits[rows[i], 0], step)
    return served, logits_out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="command-a-plus-05-2026")
    ap.add_argument("--traffic", default="offline-closed-16k-swa")
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--lengths", default=",".join(map(str, LENGTHS)),
                    help="the prompts' lengths, one a live row")
    ap.add_argument("--tokens", type=int, default=48)
    ap.add_argument("--ref-rows", type=int, default=None,
                    help="rows held to the reference (the first ones; "
                    "None: all)")
    ap.add_argument("--faults", default=",".join(FAULTS),
                    help="which faults, comma-separated ('' for none)")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "command_a_faults.json"))
    args = ap.parse_args(argv)

    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import manifest
    from benchmark.harness.families import family_of
    from benchmark.reference import cohere2_moe_ref as ref

    start = time.monotonic()

    def said(what):     # progress, on stderr: a chip call shows its tail
        print(f"[{time.monotonic() - start:7.1f} s] {what}",
              file=sys.stderr, flush=True)

    from ray_tpu.llm.engine import jit_forward
    from ray_tpu.models import family_of as program_family

    config = manifest.load_json(os.path.join(
        ROOT, "benchmark", "configs", args.config + ".json"), "config")
    check = manifest.load_json(os.path.join(
        ROOT, "benchmark", "traffic", args.traffic + ".json"),
        "traffic")["check"]
    fam = family_of(config)
    cfg = fam.program_config(config, attn_impl="dense", remat=False)
    params = fam.init(cfg, jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng([args.seed, 0x6661])
    lengths = [int(n) for n in args.lengths.split(",")]
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in lengths]
    wanted = [f for f in args.faults.split(",") if f]
    faults = [f for f in FAULTS if f in wanted]
    assert len(faults) == len(wanted), args.faults
    # the cell's own decode shape: 16 rows, all live where there are 16
    shape = dict(max_batch=max(len(lengths), 16), hole=len(lengths) < 16)
    as_it_is = jit_forward(program_family(cfg).module(cfg))
    said("weights made")
    served, _ = serve(cfg, params, prompts, args.tokens, fwd=as_it_is,
                      **shape)
    said("served as it is")
    held = range(min(args.ref_rows or len(lengths), len(lengths)))
    # The reference's logits at the generated positions only, a row at a
    # time, every row filled behind to ONE length (what lies behind a
    # position changes nothing before it), so each kind of layer compiles
    # once.
    fed = [prompts[i] + served[i][:-1] for i in held]
    longest = max(len(f) for f in fed)
    ref_rows = []
    for i, f in enumerate(fed):
        tokens = np.zeros((1, longest), np.int32)
        tokens[0, :len(f)] = f
        ref_rows.extend(np.asarray(ref.forward(
            config, params, jnp.asarray(tokens), last=args.tokens,
            lengths=[len(f)], by_layer=True)))
        said(f"reference row {i}")

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
           "gap_by_row": {str(lengths[i]): gaps(ref_rows[i], 1, served[i])
                          for i in held},
           "gap": {"as_it_is": worst(served)}}

    def written():      # after every reading: a call cut short keeps them
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)

    print(json.dumps(out), flush=True)
    written()
    # Each fault is fed the right program's tokens (one reference forward
    # serves all) and judged by the tokens IT would have served.  The
    # rounded tree goes last (it takes the tree).
    for name in sorted(faults, key=lambda f: f == "weights_8bit"):
        with fault(name, cfg, params, donate=True) as (c, p):
            would, _ = serve(
                c, p, prompts, args.tokens, forced=served,
                fwd=as_it_is if name in SAME_PROGRAM else None, **shape)
        del c, p
        out["gap"][name] = worst(would)
        said(f"served with {name}")
        print(json.dumps({name: out["gap"][name]}), flush=True)
        written()
    out["fails"] = {k: not v <= out["tolerance"]
                    for k, v in out["gap"].items()}
    written()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
