"""What STARTING one of a cell's serving programs costs, by phase, on the
chip: the engine's ``lower().compile()`` of Olmo-Hybrid's forward at the
cell's sixteen layers (what ``stats()["programs"]`` times and ``setup_s``
sums), cut into tracing, lowering, the compile cache's key, the entry's read,
its decompression and the executable's deserialisation.  No weights: the
arguments are shapes, so the first run is not in it.  (Read on the chip at
PR 57: a clean process starts a cached program in 2.2-2.4 s whatever the
form of the scan; the serving process pays more, PERF.md section 7.)

One pass a process; the second pass with the same ``--cache`` is the warm
start a cell's later runs pay:

    chiprun -- sh -c 'for n in 1 2; do python3 \\
        examples/probes/program_start_probe.py --cache /tmp/c; done'

``--tree DIR`` reads ``ray_tpu`` from another checkout (``git archive`` of
the commit to compare with); give each tree its own ``--cache``.  Prints a
line a program and appends the pass to ``--out``.  The cuts inside
``compile`` wrap private functions of this JAX (0.9); where one is missing
that cut reads 0 and ``compile_s`` still holds the whole."""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

_AP = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
_AP.add_argument("--tree", default=os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
_AP.add_argument("--cache", required=True)
_AP.add_argument("--buckets", default="8,1,256,1024",
                 help="prefill rows; 1: the decode step of 16 rows")
_AP.add_argument("--out", default="chiprun_out/program_start_probe.jsonl")
ARGS = _AP.parse_args()
os.environ["JAX_COMPILATION_CACHE_DIR"] = ARGS.cache
sys.path.insert(0, ARGS.tree)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax._src import compilation_cache, compiler, lru_cache  # noqa: E402

from ray_tpu.llm.engine import jit_forward  # noqa: E402
from ray_tpu.llm.kv_cache import init_pool, init_state, pages_for  # noqa: E402
from ray_tpu.models import MODEL_FAMILIES  # noqa: E402
from ray_tpu.models import olmo_hybrid as oh  # noqa: E402

SPENT = {}


def clocked(module, name: str, key: str) -> None:
    """``module.name`` adds its seconds to ``SPENT[key]``."""
    inner = getattr(module, name, None)
    if inner is None:
        return

    @functools.wraps(inner)
    def outer(*args, **kwargs):
        t0 = time.perf_counter()
        try:
            return inner(*args, **kwargs)
        finally:
            SPENT[key] = SPENT.get(key, 0.0) + time.perf_counter() - t0

    setattr(module, name, outer)


def main() -> None:
    clocked(compiler, "_resolve_compilation_strategy", "key_s")
    clocked(compilation_cache, "get_executable_and_time", "get_s")
    clocked(compilation_cache, "decompress_executable", "decompress_s")
    clocked(lru_cache.LRUCache, "get", "read_s")
    clocked(compiler, "backend_compile_and_load", "backend_compile_s")
    row = MODEL_FAMILIES["olmohybrid"]
    cfg = oh.OlmoHybridConfig(
        layer_types=(oh.GDN, oh.GDN, oh.GDN, oh.ATTENTION) * 4,
        attn_impl="dense", remat=False)
    spec = row.cache(cfg)
    params = jax.eval_shape(lambda: row.init(cfg, jax.random.PRNGKey(0)))
    kv = jax.eval_shape(lambda: init_pool(spec, 4096, 16, cfg.dtype))
    state = jax.eval_shape(lambda: init_state(spec, 16, cfg.dtype))
    ints = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    dev = jax.devices()[0]
    done = {"device": f"{dev.platform}:{dev.device_kind}",
            "tree": ARGS.tree, "programs": {}}
    for t in map(int, ARGS.buckets.split(",")):
        shape = (16, 1) if t == 1 else (1, t)
        b = shape[0]
        SPENT.clear()
        t0 = time.perf_counter()
        traced = jit_forward(row.module(cfg)).trace(
            params, ints(shape), kv["k_pages"], kv["v_pages"],
            ints((b, pages_for(4096, 16))), ints(shape), state["conv"],
            state["ssm"], ints((b,)),
            **({} if t == 1 else {"last": ints((b,))}))
        t1 = time.perf_counter()
        lowered = traced.lower()
        t2 = time.perf_counter()
        lowered.compile()
        t3 = time.perf_counter()
        read, unpack = (SPENT.get(k, 0.0) for k in ("read_s", "decompress_s"))
        cut = {"trace_s": t1 - t0, "lower_s": t2 - t1, "compile_s": t3 - t2,
               "key_s": SPENT.get("key_s", 0.0), "read_s": read,
               "decompress_s": unpack,
               # (a miss reads nothing and deserialises nothing)
               "deserialize_s": max(SPENT.get("get_s", 0.0) - read - unpack,
                                    0.0) if unpack else 0.0,
               "backend_compile_s": SPENT.get("backend_compile_s", 0.0),
               "stablehlo_chars": len(lowered.as_text())}
        done["programs"]["decode" if t == 1 else f"prefill[{t}]"] = {
            k: round(v, 3) if isinstance(v, float) else v
            for k, v in cut.items()}
        print(ARGS.tree, t, done["programs"][
            "decode" if t == 1 else f"prefill[{t}]"], flush=True)
    os.makedirs(os.path.dirname(ARGS.out) or ".", exist_ok=True)
    with open(ARGS.out, "a") as f:
        f.write(json.dumps(done) + "\n")


if __name__ == "__main__":
    main()
