#!/usr/bin/env python3
"""Shows that the comparison which decides ``correct`` for a Kimi-Linear
cell CAN fail (as kimi_faults.py does for Kimi-K2), at the TIMED sizes,
which the CPU child of a run cannot hold: the program as it is, and the
program with one thing wrong at a time, each served greedily through
``jit_forward`` and BOTH pools (every prompt prefilled padded to its
bucket: the chunked delta-rule scan and the expanded latent attention, the
state and the window stored at the prompt's length; then decode steps in a
batch: the recurrence once a row over its slot, the absorbed latent
attention over the pages) and held to the float32 reference as
``benchmark/harness/check.py`` holds the cell: at every generated position,
how far the served token's reference logit lies under the largest.

    python3 benchmark/tools/kimi_linear_faults.py [--layers N] [--seed S]
        [--rows 16] [--prompt 2040] [--spread 24] [--tokens 256]
        [--ref-rows 16] [--ref-block 4] [--faults a,b]
        [--out chiprun_out/kimi_linear_faults.json]

The default is the cell's shape: 16 live rows whose prompts of 1,680-2,040
tokens go through the 2,048 bucket (32 chunks of the scan) and are then
decoded to 1,936-2,296 positions, so the state has been carried through
both paths.  Program AND reference run on the default backend (the chip,
under chiprun): the reference in float32 at
``jax.default_matmul_precision("highest")``, a layer a ``jit``
(``forward(by_layer=True)``: one program a kind of layer), rows in blocks
filled to one length, its recurrence token by token, its attention in
blocks of positions, the head over the generated positions alone.  Every
reading is written to ``--out`` as it is made.

The faults (FAULTS): ``S`` held in bf16; ``beta`` = 1; ``a`` = 1 (no
decay); the decay averaged over a head's channels (a scalar a head); the
``k (S^T k)`` correction left out (plain gated linear attention); the L2
norms left out; a window one tap short; the output gate left out; the
state not carried from the prefill into the first decode step; padded
positions updating the state; a rotation applied to the latent layers' 64;
``routed_scaling_factor`` left out; the shared expert left out; the
selection bias left out; every matrix rounded to 8 bits (float8 e4m3: the
nearest precision below the one the configuration states).
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

FAULTS = ("state_bf16", "beta_one", "no_decay", "scalar_decay",
          "no_correction", "no_l2norm", "window_short", "no_out_gate",
          "state_not_carried", "padded_update", "mla_rotated",
          "no_routed_scaling", "no_shared_expert", "no_select_bias",
          "weights_8bit")
# These change the tree or the serving alone: the program's text stays,
# and one jitted forward serves them all.
SAME_PROGRAM = ("no_decay", "window_short", "no_out_gate",
                "state_not_carried", "no_shared_expert", "no_select_bias",
                "weights_8bit")


def _uncorrected(kl):
    """``kda_scan`` and ``kda_step`` without the ``k (S^T k)`` term: ``S_t =
    diag(a_t) S_{t-1} + beta_t k_t v_t^T``, token by token."""
    import jax
    import jax.numpy as jnp

    def scan(q, k, v, g, beta, chunk, sub, state=None):
        b, _, h, d = q.shape

        def step(s, x):
            q_t, k_t, v_t, g_t, beta_t = x
            s = jnp.exp(g_t)[..., None] * s \
                + (beta_t[..., None] * k_t)[..., None] * v_t[..., None, :]
            return s, jnp.sum(s * q_t[..., None], axis=-2)

        s0 = jnp.zeros((b, h, d, d), jnp.float32) if state is None \
            else state
        s, o = jax.lax.scan(step, s0, tuple(
            jnp.moveaxis(z, 1, 0) for z in (q, k, v, g, beta)))
        return jnp.moveaxis(o, 0, 1), s

    def step(pool, layer, slots, fresh, q, k, v, a, beta):
        s = kl._load_states(pool, layer, slots)
        s = jnp.where(fresh[:, None, None, None], 0.0, s)
        s = a[..., None] * s \
            + (beta[..., None] * k)[..., None] * v[..., None, :]
        o = jnp.sum(s * q[..., None], axis=-2)
        return o, pool.at[layer, slots].set(s.astype(pool.dtype),
                                            mode="drop")

    return scan, step


@contextlib.contextmanager
def fault(name, cfg, params, donate=False):
    """Yields (cfg, params, how to serve: ``ssm_dtype`` and
    ``drop_state``) with ``name`` wrong (None: nothing wrong).
    ``donate``: a fault that rewrites every matrix takes the caller's
    buffers for it (at the published sizes the chip cannot hold the tree
    twice)."""
    import jax.numpy as jnp

    import ray_tpu.models.kimi as kimi
    import ray_tpu.models.kimi_linear as kl

    how = {"ssm_dtype": jnp.float32, "drop_state": False}
    if name == "weights_8bit":      # the nearest precision below bf16:
        with granite_faults.fault(  # the rounding is of the tree alone
                name, cfg, params, donate) as (cfg, params, _):
            yield cfg, params, how
        return
    undo = []

    def patch(module, attr, new):
        undo.append((module, attr, getattr(module, attr)))
        setattr(module, attr, new)

    def both(edit_scan, edit_step):
        """``kda_scan`` given (g, beta) edited, ``kda_step`` (a, beta)."""
        scan, step = kl.kda_scan, kl.kda_step
        patch(kl, "kda_scan", lambda q, k, v, g, beta, *rest: scan(
            q, k, v, *edit_scan(g, beta), *rest))
        patch(kl, "kda_step",
              lambda pool, layer, slots, fresh, q, k, v, a, beta: step(
                  pool, layer, slots, fresh, q, k, v, *edit_step(a, beta)))

    if name == "state_bf16":
        how["ssm_dtype"] = jnp.bfloat16
    elif name == "beta_one":        # (0 at a padded position stays)
        def one(x, beta):
            return x, jnp.where(beta > 0, 1.0, 0.0)
        both(one, one)
    elif name == "no_decay":        # exp(A_log) = 0: g = 0, a = 1
        params = _edit(params, "A_log",
                       lambda _, w: jnp.full_like(w, -jnp.inf))
    elif name == "scalar_decay":    # the sibling rule: a scalar a head
        both(lambda g, beta: (jnp.broadcast_to(
            jnp.mean(g, -1, keepdims=True), g.shape), beta),
            lambda a, beta: (jnp.broadcast_to(jnp.exp(jnp.mean(
                jnp.log(a), -1, keepdims=True)), a.shape), beta))
    elif name == "no_correction":
        scan, step = _uncorrected(kl)
        patch(kl, "kda_scan", scan)
        patch(kl, "kda_step", step)
    elif name == "no_l2norm":
        patch(kl, "_l2_normalised", lambda x: x)
    elif name == "window_short":    # the oldest tap left out
        params = _edit(params, "conv_w", lambda _, w: w.at[0].set(0))
    elif name == "no_out_gate":     # sigmoid(0) = 1/2, made 1 in W_o
        params = _edit(params, "kernel", lambda path, w: jnp.zeros_like(w)
                       if path.endswith("/g_b/kernel")
                       else 2 * w if path.endswith("/kda/wo/kernel") else w)
    elif name == "state_not_carried":
        how["drop_state"] = True
    elif name == "padded_update":   # padding decays and writes as a token
        both(lambda g, beta: (jnp.where(beta[..., None] > 0, g, -0.05),
                              jnp.where(beta > 0, beta, 0.5)),
             lambda a, beta: (a, beta))
    elif name == "mla_rotated":
        from ray_tpu.models.layers import _rope

        real = kimi.latent_attention

        def rotated(cfg_, q_nope, q_pe, c_kv, k_pe, w_kvb, scale,
                    cache=None):
            pos = None if cache is None else cache["positions"]
            return real(cfg_, q_nope, _rope(q_pe, 10000.0, pos), c_kv,
                        _rope(k_pe[:, :, None], 10000.0, pos)[:, :, 0],
                        w_kvb, scale, cache)
        patch(kimi, "latent_attention", rotated)
    elif name == "no_routed_scaling":
        cfg = dataclasses.replace(cfg, routed_scaling_factor=1.0)
    elif name == "no_shared_expert":
        params = _edit(params, "kernel", lambda path, w: jnp.zeros_like(w)
                       if path.endswith("/shared_down/kernel") else w)
    elif name == "no_select_bias":
        params = _edit(params, "expert_bias",
                       lambda _, w: jnp.zeros_like(w))
    elif name is not None:
        raise ValueError(name)
    try:
        yield cfg, params, how
    finally:
        for module, attr, old in reversed(undo):
            setattr(module, attr, old)


def serve(cfg, params, prompts, n_tokens, max_batch=None, page=16,
          forced=None, hole=True, ssm_dtype=None, drop_state=False,
          fwd=None):
    """Greedy tokens and their logits for ``prompts`` through the
    engine's jitted forward and both pools: each prompt prefilled ([1,
    bucket], padded) into the slot of its row, then all decoded together
    in a [max_batch, 1] batch with row 1 left EMPTY (a hole;
    ``hole=False``: every row live, the cell's full batch); pages and
    slots start from other numbers than zeros (they changed hands).
    With ``forced`` (tokens per prompt) those are fed instead of the
    argmax (teacher forcing), and the argmax is still what is returned.
    ``ssm_dtype``: what the state pool holds ``S`` in (None: float32);
    ``drop_state``: the state zeroed between the prefills and the first
    decode step; ``fwd``: a jitted forward to use again."""
    import numpy as np

    from ray_tpu.llm.engine import _bucket, jit_forward
    from ray_tpu.llm.kv_cache import init_pool, init_state, pages_for
    from ray_tpu.models import family_of

    fam = family_of(cfg)
    spec = fam.cache(cfg)
    rows = [0] + list(range(2, len(prompts) + 1)) if hole \
        else list(range(len(prompts)))                  # row 1: the hole
    max_batch = max_batch or len(prompts) + 2
    longest = max(len(p) for p in prompts) + n_tokens
    per_seq = pages_for(longest, page)
    (pages,) = (a + 1 for a in init_pool(spec, per_seq * max_batch, page,
                                         cfg.dtype).values())
    state = init_state(spec, max_batch, cfg.dtype)
    conv, ssm = state["conv"] + 1, (state["ssm"] + 1).astype(
        ssm_dtype or state["ssm"].dtype)
    fwd = fwd or jit_forward(fam.module(cfg))
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
        nonlocal pages, conv, ssm
        logits, pages, conv, ssm, *_ = fwd(params, toks, pages, table_rows,
                                           pos, conv, ssm, slots)
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
    if drop_state:
        ssm = ssm * 0
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
    ap.add_argument("--config", default="kimi-linear-48b-a3b")
    ap.add_argument("--traffic", default="offline-closed-longout")
    ap.add_argument("--layers", type=int, default=None,
                    help="the first N layers of the published list")
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--rows", type=int, default=16,
                    help="live rows of the decode batch")
    ap.add_argument("--prompt", type=int, default=2040,
                    help="the longest prompt; row i has --spread x i fewer")
    ap.add_argument("--spread", type=int, default=24)
    ap.add_argument("--tokens", type=int, default=256)
    ap.add_argument("--ref-rows", type=int, default=None,
                    help="rows held to the reference (the first ones; "
                    "None: all)")
    ap.add_argument("--ref-block", type=int, default=4,
                    help="rows of one reference forward")
    ap.add_argument("--faults", default=",".join(FAULTS),
                    help="which faults, comma-separated ('' for none)")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "kimi_linear_faults.json"))
    args = ap.parse_args(argv)

    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import manifest
    from benchmark.harness.families import family_of
    from benchmark.reference import kimi_linear_ref as ref

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
    if args.layers:
        lin = config["linear_attn_config"]
        config["num_hidden_layers"] = args.layers
        for key in ("kda_layers", "full_attn_layers"):
            lin[key] = [i for i in lin[key] if i <= args.layers]
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
    as_it_is = jit_forward(program_family(cfg).module(cfg))
    said("weights made")
    served, _ = serve(cfg, params, prompts, args.tokens, fwd=as_it_is,
                      **shape)
    said("served as it is")
    held = range(min(args.ref_rows or args.rows, args.rows))
    # The reference's logits at the generated positions only: the rows in
    # blocks of --ref-block, every block filled behind to ONE length (what
    # lies behind a position changes nothing before it), so each kind of
    # layer compiles once.
    fed = [prompts[i] + served[i][:-1] for i in held]
    longest = max(len(f) for f in fed)
    ref_rows = []
    for lo in range(0, len(fed), args.ref_block):
        rows = fed[lo:lo + args.ref_block]
        tokens = np.zeros((len(rows), longest), np.int32)
        for j, f in enumerate(rows):
            tokens[j, :len(f)] = f
        ref_rows.extend(np.asarray(ref.forward(
            config, params, jnp.asarray(tokens), last=args.tokens,
            lengths=[len(f) for f in rows], by_layer=True)))
        said(f"reference rows {lo}..{lo + len(rows) - 1}")

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

    def written():      # after every reading: a call cut short keeps them
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)

    print(json.dumps(out), flush=True)
    written()
    # Each fault is fed the right program's tokens (one reference
    # forward serves all) and judged by the tokens IT would have served.
    # Those the one program serves go first, the rounded tree last of them
    # (it takes the tree, which is then drawn again from the seed); then
    # those that compile a program of their own.
    faults = sorted(faults, key=lambda f: (f not in SAME_PROGRAM,
                                           f == "weights_8bit"))
    for name in faults:
        with fault(name, cfg, params, donate=True) as (c, p, how):
            would, _ = serve(
                c, p, prompts, args.tokens, forced=served,
                fwd=as_it_is if name in SAME_PROGRAM else None,
                **how, **shape)
        del c, p
        if name == "weights_8bit":
            params = fam.init(cfg, jax.random.PRNGKey(args.seed))
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
