#!/usr/bin/env python3
"""Shows that the comparison which decides ``correct`` for a Xing4.0 cell
CAN fail (as kimi_faults.py does for Kimi-K2, whose ``serve`` and measure
it takes), at the TIMED sizes, which the CPU child of a run cannot hold:
the program as it is, and the program with one thing wrong at a time, each
served greedily through ``jit_forward`` and the latent pool (every prompt
prefilled padded to its bucket, then decode steps in a full batch) and held
to the float32 reference as ``benchmark/harness/check.py`` holds the cell:
at every generated position, how far the served token's reference logit
lies under the largest.

    python3 benchmark/tools/xing_faults.py [--layers N] [--seed S]
        [--rows 16] [--prompt 3600] [--spread 40] [--tokens 48]
        [--ref-rows 16] [--faults a,b] [--out chiprun_out/xing_faults.json]

The default is the cell's shape: 16 live rows at 3,000-3,648 positions
(prompts of 3600, 3560, ... tokens, each through the 4,096 bucket).
Program AND reference run on the default backend (the chip, under
chiprun): the reference in float32 at ``jax.default_matmul_precision(
"highest")``, a layer a ``jit`` (``forward(by_layer=True)``), the logits
of the generated positions alone.

The faults (FAULTS), of the residual path: the Sinkhorn left out (``exp(S)``
alone); 1 round for 20; rows-only normalisation; ``H_res`` transposed;
``H_post``'s 2 left out; the input-dependent part of the maps left out
(``a_* = 0``); the flattened norm left out; the streams averaged before
every sublayer (one stream in four copies); the exit taking stream 0 for
the sum.  Of the shared code (kimi_faults.py's hooks): ``k_pe`` left out
of the scores; the shared expert left out.  And every matrix rounded to 8
bits (float8 e4m3: the nearest precision below the one the configuration
states).  Prints one JSON object: the gap of each against the traffic
file's tolerance; every reading is written to ``--out`` as it is made."""

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

from benchmark.tools import kimi_faults  # noqa: E402
from benchmark.tools.kimi_faults import gaps, serve  # noqa: E402,F401
from benchmark.tools.lfm2_faults import _edit  # noqa: E402

KIMI = ("no_k_pe", "no_shared_expert", "weights_8bit")
FAULTS = ("no_sinkhorn", "sinkhorn_1_round", "sinkhorn_rows_only",
          "h_res_transposed", "no_h_post_2", "maps_input_independent",
          "no_flat_norm", "streams_averaged", "exit_stream_0") + KIMI


@contextlib.contextmanager
def fault(name, cfg, params, donate=False):
    """Yields (cfg, params) with ``name`` wrong (None: nothing wrong).
    ``donate``: as kimi_faults.py's."""
    import jax.numpy as jnp

    import ray_tpu.models.xing as xing

    if name in KIMI:
        with kimi_faults.fault(name, cfg, params, donate) as out:
            yield out
        return
    undo = []

    def patch(attr, new):
        undo.append((attr, getattr(xing, attr)))
        setattr(xing, attr, new)

    def kind(**parts):      # ``XingConfig.residual`` gives ``xing.HC``
        patch("HC", dataclasses.replace(xing.HC, **parts))

    def averaged(x):
        return jnp.broadcast_to(jnp.mean(x.astype(jnp.float32), axis=2,
                                         keepdims=True).astype(x.dtype),
                                x.shape)

    real = xing.HC
    if name == "no_sinkhorn":
        cfg = dataclasses.replace(cfg, hc_sinkhorn_iters=0)
    elif name == "sinkhorn_1_round":
        cfg = dataclasses.replace(cfg, hc_sinkhorn_iters=1)
    elif name == "sinkhorn_rows_only":
        def rows_only(m, iters, eps):
            for _ in range(iters):
                m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
            return m
        patch("sinkhorn", rows_only)
    elif name == "h_res_transposed":
        sinkhorn = xing.sinkhorn
        patch("sinkhorn", lambda m, iters, eps: jnp.swapaxes(
            sinkhorn(m, iters, eps), 0, 1))
    elif name == "no_h_post_2":
        kind(write=lambda cfg, x, maps, y: real.write(
            cfg, x, (maps[0] / 2.0, maps[1]), y))
    elif name == "maps_input_independent":
        params = _edit(params, "map_gate", lambda _, w: jnp.zeros_like(w))
    elif name == "no_flat_norm":
        class Skipped(xing.RMSNorm):
            def __call__(self, x):
                if self.name == "norm":
                    return x.astype(self.dtype)
                return super().__call__(x)
        patch("RMSNorm", Skipped)
    elif name == "streams_averaged":
        kind(read=lambda cfg, name: (lambda x, live: real.read(
            cfg, name=name)(averaged(x), live)),
            write=lambda cfg, x, maps, y: real.write(
                cfg, averaged(x), maps, y))
    elif name == "exit_stream_0":
        kind(end=lambda cfg, x: x[:, :, 0])
    elif name is not None:
        raise ValueError(name)
    try:
        yield cfg, params
    finally:
        for attr, old in reversed(undo):
            setattr(xing, attr, old)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="xing4.0-29b-a4b")
    ap.add_argument("--traffic", default="offline-closed-4k-hc")
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
        ROOT, "chiprun_out", "xing_faults.json"))
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import manifest
    from benchmark.harness.families import family_of
    from benchmark.reference import xing4_0_ref as ref

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
    # (every row filled to the longest's length: one compile a layer kind)
    longest = max(lengths) + args.tokens - 1
    ref_rows = []
    for i in held:
        seq = prompts[i] + served[i][:-1]
        ref_rows.append(np.asarray(ref.forward(
            config, params, jnp.asarray(
                [seq + [0] * (longest - len(seq))], jnp.int32),
            last=args.tokens, lengths=[len(seq)], by_layer=True)[0]))

    def worst(tokens):
        return max(gaps(ref_rows[i], 1, tokens[i]) for i in held)

    out = {"backend": jax.default_backend(), "seed": args.seed,
           "layers": config["num_hidden_layers"],
           "tolerance": check["logit_tolerance"],
           "positions": [n + args.tokens for n in lengths],
           "rows_held_to_the_reference": len(held),
           "logit_std": float(np.std(ref_rows[0])),
           "argmax_agree": float(np.mean([
               np.mean(ref_rows[i].argmax(-1) == np.array(served[i]))
               for i in held])),
           "gap": {"as_it_is": worst(served)}}

    def keep():
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)

    print(json.dumps(out), flush=True)
    keep()
    # Each fault is fed the right program's tokens (one reference
    # forward serves all) and judged by the tokens IT would have served.
    assert FAULTS[-1] == "weights_8bit"     # it takes the tree: last
    for name in faults:
        with fault(name, cfg, params, donate=True) as (c, p):
            would, _ = serve(c, p, prompts, args.tokens, forced=served,
                             **shape)
        out["gap"][name] = worst(would)
        print(json.dumps({name: out["gap"][name]}), flush=True)
        keep()
    out["fails"] = {k: not v <= out["tolerance"]
                    for k, v in out["gap"].items()}
    keep()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
