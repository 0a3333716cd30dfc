#!/usr/bin/env python3
"""Shows that the comparison which decides ``correct`` for an Olmo-Hybrid
cell CAN fail (as kimi_linear_faults.py does for Kimi-Linear), at the TIMED
sizes, which the CPU child of a run cannot hold: the program as it is, and
the program with one thing wrong at a time, each served greedily through
``jit_forward`` and BOTH pools (every prompt prefilled padded to its bucket
as the engine prefills, ``last=`` the prompt's last position and
``logits[0, 0]`` read: the chunked delta-rule scan and the K/V prefill among
its own rows, the state and the window stored at the prompt's length; then
decode steps in a batch: the recurrence once a row over its slot, the paged
attention over the pages) and held to the float32 reference as
``benchmark/harness/check.py`` holds the cell: at every generated position,
how far the served token's reference logit lies under the largest.

    python3 benchmark/tools/olmo_hybrid_faults.py [--layers N] [--seed S]
        [--rows 16] [--prompt 3800] [--spread 50] [--tokens 128]
        [--ref-rows 16] [--ref-block 2] [--faults a,b]
        [--out chiprun_out/olmo_hybrid_faults.json]

The default is the cell's shape: 16 live rows whose prompts of 3,050-3,800
tokens go through the 4,096 bucket (64 chunks of the scan, the flash
kernel) and are then decoded 128 steps, so the state has been carried
through both paths.  Program AND reference run on the default backend (the
chip, under chiprun): the reference in float32 at
``jax.default_matmul_precision("highest")``, a layer a ``jit``
(``forward(by_layer=True)``), rows in blocks filled to one length, its
recurrence token by token, its attention in blocks of positions, the head
over the generated positions alone.  Every reading is written to ``--out``
as it is made.

The faults (FAULTS): ``beta`` without its factor 2; the decay left out; a
decay of its own a key channel; the sublayer norm before instead of after;
the QK-norm left out; a rotation applied; the output gate a sigmoid; the L2
norms left out; the ``k (S~^T k)`` correction left out; the state not
carried from the prefill into the first decode step; padded positions
updating the state; a window one tap short; ``d_k`` and ``d_v`` exchanged in
q's scale; the K/V prefill attending one position past its causal triangle;
every matrix rounded to 8 bits (float8 e4m3: the nearest precision below
the one the configuration states).
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
from benchmark.tools.lfm2_faults import _edit  # noqa: E402

FAULTS = ("beta_one", "no_decay", "channel_decay", "norm_before",
          "no_qk_norm", "rotary", "gate_sigmoid", "no_l2norm",
          "no_correction", "state_not_carried", "padded_update",
          "window_short", "scale_swapped", "prefill_past_causal",
          "weights_8bit")
# These change the tree or the serving alone: the program's text stays,
# and one jitted forward serves them all.
SAME_PROGRAM = ("no_decay", "state_not_carried", "window_short",
                "weights_8bit")


def _uncorrected():
    """``kda_scan`` and ``kda_step`` without the ``k (S~^T k)`` term: ``S_t
    = a_t S_{t-1} + beta_t k_t v_t^T``, token by token."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.delta_rule import pack_states, unpack_states

    def scan(q, k, v, g, beta, chunk, sub, state=None):
        b, _, h, dk = q.shape

        def step(s, x):
            q_t, k_t, v_t, g_t, beta_t = x
            s = jnp.exp(g_t)[..., None] * s \
                + (beta_t[..., None] * k_t)[..., None] * v_t[..., None, :]
            return s, jnp.sum(s * q_t[..., None], axis=-2)

        s0 = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32) \
            if state is None else state
        s, o = jax.lax.scan(step, s0, tuple(
            jnp.moveaxis(z, 1, 0) for z in (q, k, v, g, beta)))
        return jnp.moveaxis(o, 0, 1), s

    def step(pool, layer, slots, fresh, q, k, v, a, beta):
        s = unpack_states(pool.at[layer, slots].get(mode="clip"),
                          q.shape[1]).astype(jnp.float32)
        s = jnp.where(fresh[:, None, None, None], 0.0, s)
        s = a[..., None] * s \
            + (beta[..., None] * k)[..., None] * v[..., None, :]
        o = jnp.sum(s * q[..., None], axis=-2)
        return o, pool.at[layer, slots].set(
            pack_states(s, pool.shape[2]).astype(pool.dtype), mode="drop")

    return scan, step


@contextlib.contextmanager
def fault(name, cfg, params, donate=False):
    """Yields (cfg, params, how to serve: ``drop_state``) with ``name``
    wrong (None: nothing wrong).  ``donate``: a fault that rewrites every
    matrix takes the caller's buffers for it (at the published sizes the
    chip cannot hold the tree twice)."""
    import jax.numpy as jnp

    import ray_tpu.models.attention as attention
    import ray_tpu.models.olmo_hybrid as oh
    from ray_tpu.models.decoder import Attention, attention_kind

    how = {"drop_state": False}
    if name == "weights_8bit":      # the nearest precision below bf16:
        with granite_faults.fault(  # the rounding is of the tree alone
                name, cfg, params, donate) as (cfg, params, _):
            yield cfg, params, how
        return
    undo = []
    absent = object()

    def patch(owner, attr, new):
        undo.append((owner, attr, owner.__dict__.get(attr, absent)
                     if isinstance(owner, type) else getattr(owner, attr)))
        setattr(owner, attr, new)

    def both(edit_scan, edit_step):
        """``kda_scan`` given (q, g, beta) edited, ``kda_step`` (q, a,
        beta)."""
        scan, step = oh.kda_scan, oh.kda_step

        def scan_(q, k, v, g, beta, *rest):
            q, g, beta = edit_scan(q, g, beta)
            return scan(q, k, v, g, beta, *rest)

        def step_(pool, layer, slots, fresh, q, k, v, a, beta):
            q, a, beta = edit_step(q, a, beta)
            return step(pool, layer, slots, fresh, q, k, v, a, beta)

        patch(oh, "kda_scan", scan_)
        patch(oh, "kda_step", step_)

    def attention_with(**how_):
        kinds = dict(oh.MIXERS)
        kinds[oh.ATTENTION] = attention_kind(functools.partial(
            Attention, **{**dict(rope=False, qk_norm="width"), **how_}))
        patch(oh, "MIXERS", kinds)

    if name == "beta_one":          # sigmoid, without the factor 2
        def half(q, x, beta):
            return q, x, 0.5 * beta
        both(half, half)
    elif name == "no_decay":        # exp(A_log) = 0: g = 0, a = 1
        params = _edit(params, "A_log",
                       lambda _, w: jnp.full_like(w, -jnp.inf))
    elif name == "channel_decay":   # the sibling rule: a decay a channel
        def spread(dk):             # 0.5 .. 1.5 of the head's log-decay
            return jnp.linspace(0.5, 1.5, dk, dtype=jnp.float32)
        both(lambda q, g, beta: (q, g * spread(q.shape[-1]), beta),
             lambda q, a, beta: (q, jnp.exp(jnp.log(a)
                                            * spread(q.shape[-1])), beta))
    elif name == "norm_before":     # x + f(norm(x)), the other families'
        patch(oh.OlmoHybridConfig, "norm_output", False)
    elif name == "no_qk_norm":
        attention_with(qk_norm=None)
    elif name == "rotary":
        patch(oh.OlmoHybridConfig, "rope_theta", 10000.0)
        attention_with(rope=True)
    elif name == "gate_sigmoid":
        import jax

        patch(oh, "_out_gate", jax.nn.sigmoid)
    elif name == "no_l2norm":
        patch(oh, "_l2_normalised", lambda x: x)
    elif name == "no_correction":
        scan, step = _uncorrected()
        patch(oh, "kda_scan", scan)
        patch(oh, "kda_step", step)
    elif name == "state_not_carried":
        how["drop_state"] = True
    elif name == "padded_update":   # padding decays and writes as a token
        both(lambda q, g, beta: (q, jnp.where(beta[..., None] > 0, g, -0.05),
                                 jnp.where(beta > 0, beta, 0.5)),
             lambda q, a, beta: (q, a, beta))
    elif name == "window_short":    # the oldest tap left out
        params = _edit(params, "conv_w", lambda _, w: w.at[0].set(0))
    elif name == "scale_swapped":   # q * d_v ** -0.5 for d_k ** -0.5
        def swapped(q, x, beta):
            return q * (cfg.gdn_key_dim / cfg.gdn_value_dim) ** 0.5, x, beta
        both(swapped, swapped)
    elif name == "prefill_past_causal":
        real = attention._attention

        def one_past(cfg_, q, k, v, *rest):
            """Row i sees keys 1 .. i + 1 (and the last row the first
            key): one position past its causal triangle."""
            return real(cfg_, q, jnp.roll(k, -1, axis=1),
                        jnp.roll(v, -1, axis=1), *rest)
        patch(attention, "_attention", one_past)
    elif name is not None:
        raise ValueError(name)
    try:
        yield cfg, params, how
    finally:
        for owner, attr, old in reversed(undo):
            if old is absent:
                delattr(owner, attr)
            else:
                setattr(owner, attr, old)


def serve(cfg, params, prompts, n_tokens, max_batch=None, page=16,
          forced=None, hole=True, drop_state=False, fwd=None):
    """Greedy tokens and their logits for ``prompts`` through the
    engine's jitted forward and both pools: each prompt prefilled ([1,
    bucket], padded, ``last=`` its last position as the engine passes it)
    into the slot of its row, then all decoded together in a [max_batch, 1]
    batch with row 1 left EMPTY (a hole; ``hole=False``: every row live, the
    cell's full batch); pages and slots start from other numbers than zeros
    (they changed hands).  With ``forced`` (tokens per prompt) those are fed
    instead of the argmax (teacher forcing), and the argmax is still what is
    returned.  ``drop_state``: the state zeroed between the prefills and the
    first decode step; ``fwd``: a jitted forward to use again."""
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
    k_pages, v_pages = (a + 1 for a in init_pool(
        spec, per_seq * max_batch, page, cfg.dtype).values())
    conv, ssm = (a + 1 for a in init_state(spec, max_batch,
                                           cfg.dtype).values())
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

    def run(toks, table_rows, pos, slots, **last):
        nonlocal k_pages, v_pages, conv, ssm
        logits, k_pages, v_pages, conv, ssm, *_ = fwd(
            params, toks, k_pages, v_pages, table_rows, pos, conv, ssm,
            slots, **last)
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
                     np.array([row], np.int32),
                     last=np.array([n - 1], np.int32))
        take(i, logits[0, 0], 0)
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
    ap.add_argument("--config", default="olmo-hybrid-7b")
    ap.add_argument("--traffic", default="offline-closed-4k-gdn")
    ap.add_argument("--layers", type=int, default=None,
                    help="the first N layers of the list")
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--rows", type=int, default=16,
                    help="live rows of the decode batch")
    ap.add_argument("--prompt", type=int, default=3800,
                    help="the longest prompt; row i has --spread x i fewer")
    ap.add_argument("--spread", type=int, default=50)
    ap.add_argument("--tokens", type=int, default=128)
    ap.add_argument("--ref-rows", type=int, default=None,
                    help="rows held to the reference (the first ones; "
                    "None: all)")
    ap.add_argument("--ref-block", type=int, default=2,
                    help="rows of one reference forward")
    ap.add_argument("--faults", default=",".join(FAULTS),
                    help="which faults, comma-separated ('' for none)")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "olmo_hybrid_faults.json"))
    args = ap.parse_args(argv)

    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import manifest
    from benchmark.harness.families import family_of
    from benchmark.reference import olmo_hybrid_ref as ref

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
        config["num_hidden_layers"] = args.layers
        config["layer_types"] = config["layer_types"][:args.layers]
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
