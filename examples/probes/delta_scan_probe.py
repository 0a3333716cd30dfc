"""The chunked delta-rule scan on the chip, ``kda_scan``
(ray_tpu/models/kimi_linear.py), at both families' shapes, by positions and
by chunk: one layer's scan ALONE, or (``--layers N``) a forward of N mixer
layers at the family's widths, which is what chooses a chunk: alone the scan
pays layout copies that a program's neighbours absorb.

    chiprun -- python3 examples/probes/delta_scan_probe.py
    chiprun -- python3 examples/probes/delta_scan_probe.py --layers 3
    python3 examples/probes/delta_scan_probe.py --positions 256 --calls 2

(the last: a rehearsal on the CPU).  ``--tree DIR`` reads ``ray_tpu`` from
another checkout: the form before a change is ``git archive <commit>``
unpacked there, and no copy of it is kept here.  ``--inverse highest``
makes the matmuls of ``_unit_lower_inverse`` at the highest precision, the
rest as it is: what the default precision's rounding of the inverse costs
in error and saves in time.

Prints one line a reading and writes ``--out``: milliseconds a call (the
least of three rounds of ``--calls`` calls, each round ended by
``block_until_ready``) and, at ``--check`` positions, the largest error of
one layer's outputs and state against the token-by-token recurrence,
relative to their largest value.  The tables beside ``gdn_chunk``
(models/olmo_hybrid.py) and ``kda_chunk`` (models/kimi_linear.py) are this
probe's readings."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_AP = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
_AP.add_argument("--tree", default=os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
_AP.add_argument("--families", default="olmo,kimi")
_AP.add_argument("--positions", default="1024,2048,4096")
_AP.add_argument("--chunks", default="32,64,128")
_AP.add_argument("--layers", type=int, default=0,
                 help="0: the scan alone; N: a forward of N mixer layers")
_AP.add_argument("--inverse", default="default",
                 choices=("default", "highest"))
_AP.add_argument("--check", type=int, default=512,
                 help="positions of the error reading; 0: none")
_AP.add_argument("--calls", type=int, default=10)
_AP.add_argument("--seed", type=int, default=0)
_AP.add_argument("--out", default="chiprun_out/delta_scan_probe.json")
ARGS = _AP.parse_args() if __name__ == "__main__" else _AP.parse_args([])
sys.path.insert(0, ARGS.tree)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from ray_tpu.models import kimi_linear, olmo_hybrid  # noqa: E402

# heads, d_k, d_v, blocks inside a chunk, one decay a head, largest beta
SHAPES = {"olmo": (30, 96, 192, 8, True, 2.0),
          "kimi": (32, 128, 128, 16, False, 1.0)}


def recurrence(q, k, v, g, beta, state):
    """The delta rule a token at a time, multiplies and sums in float32."""
    def one(s, x):
        q_i, k_i, v_i, g_i, b_i = x
        s = jnp.exp(g_i)[..., None] * s
        err = v_i - jnp.sum(s * k_i[..., None], axis=-2)
        s = s + (b_i[..., None] * k_i)[..., None] * err[..., None, :]
        return s, jnp.sum(s * q_i[..., None], axis=-2)

    s, o = jax.lax.scan(one, state, tuple(
        jnp.moveaxis(z, 1, 0) for z in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), s


def drawn(seed: int, family: str, t: int):
    """One sequence as a mixer hands it over: q and k normalised, q scaled,
    a token's log-decay in about -1.6 .. -0.001, beta under the family's
    largest; and a carried state."""
    h, dk, dv, _, a_head, beta_hi = SHAPES[family]
    rng = np.random.default_rng(seed)
    q, k = (rng.normal(size=(1, t, h, dk)) for _ in range(2))
    q, k = (x / np.linalg.norm(x, axis=-1, keepdims=True) for x in (q, k))
    g = -np.exp(rng.uniform(np.log(0.001), np.log(1.6),
                            (1, t, h, 1 if a_head else dk)))
    drawn_ = (q * dk ** -0.5, k, rng.normal(size=(1, t, h, dv)), g,
              rng.uniform(0, beta_hi, (1, t, h)),
              rng.normal(size=(1, h, dk, dv)))
    return tuple(jnp.asarray(x, jnp.float32) for x in drawn_)


def timed(fn, args, calls: int) -> float:
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / calls * 1e3)
    return best


def forward_of(family: str, layers: int, chunk: int):
    """A jitted forward of ``layers`` mixer layers at the family's widths
    with this chunk, over a vocabulary of 2,048, and its arguments' maker."""
    if family == "olmo":
        cfg = olmo_hybrid.OlmoHybridConfig(
            layer_types=(olmo_hybrid.GDN,) * layers, vocab_size=2048,
            gdn_chunk=chunk, remat=False)
        model, init = olmo_hybrid.OlmoHybrid, olmo_hybrid.olmo_hybrid_init
    else:
        cfg = kimi_linear.KimiLinearConfig(
            layer_types=(kimi_linear.KDA,) * layers, vocab_size=2048,
            n_dense_layers=layers, kda_chunk=chunk, remat=False)
        model, init = kimi_linear.KimiLinear, kimi_linear.kimi_linear_init
    params = init(cfg, jax.random.PRNGKey(1))

    def inputs(seed: int, t: int):
        return params, jnp.asarray(np.random.default_rng(seed).integers(
            0, 2048, (1, t)), jnp.int32)

    return jax.jit(lambda p, tokens: model(cfg).apply(p, tokens)), inputs


def main() -> None:
    args = ARGS
    if args.inverse == "highest":
        at_default = kimi_linear._unit_lower_inverse

        def at_highest(a, block):
            with jax.default_matmul_precision("highest"):
                return at_default(a, block)

        kimi_linear._unit_lower_inverse = at_highest
    dev = jax.devices()[0]
    out = {"device": f"{dev.platform}:{dev.device_kind}", "tree": args.tree,
           "layers": args.layers, "inverse": args.inverse, "ms": {},
           "error": {}}
    for family in args.families.split(","):
        sub = SHAPES[family][3]
        for chunk in map(int, args.chunks.split(",")):
            scan = jax.jit(lambda *a, c=chunk: kimi_linear.kda_scan(
                *a[:5], c, sub, a[5]))
            fn, inputs = scan, lambda seed, t: drawn(seed, family, t)
            if args.layers:
                fn, inputs = forward_of(family, args.layers, chunk)
            for t in map(int, args.positions.split(",")):
                key = f"{family}.T{t}.chunk{chunk}"
                out["ms"][key] = round(
                    timed(fn, inputs(args.seed + t, t), args.calls), 3)
                print(key, out["ms"][key], "ms", flush=True)
            if args.check:
                drawn_ = drawn(args.seed, family, args.check)
                want = [np.asarray(x, np.float64)
                        for x in jax.jit(recurrence)(*drawn_)]
                key = f"{family}.T{args.check}.chunk{chunk}"
                out["error"][key] = [
                    float(np.max(np.abs(np.asarray(x, np.float64) - y))
                          / np.max(np.abs(y)))
                    for x, y in zip(scan(*drawn_), want)]
                print(key, "error (o, state)", out["error"][key], flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"device": out["device"], "readings": len(out["ms"])}))


if __name__ == "__main__":
    main()
