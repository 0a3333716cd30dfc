"""Headline benchmark: GPT-2 pretraining throughput on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
Metric is tokens/sec/chip for a GPT-2 (124M) training step, the
BASELINE.json headline.  vs_baseline = achieved MFU / 0.35 (the north
star: >=35% MFU GPT-2 pretrain with no CUDA in the wheel).

Tuned config (measured on v5e, round 2): batch 16, pallas flash
attention with whole-sequence blocks (ops/flash_attention.py), remat on
(HBM-bandwidth-bound regime: smaller live activations beat recompute
cost), plain fused cross entropy.  Round-1 dense-attention config was
73.7k tok/s (32% MFU); the flash kernel lifts it ~1.5x.
"""

import json
import time

import jax
import jax.numpy as jnp

from ray_tpu.util import compile_cache as _compile_cache
from ray_tpu.util import goodput as _goodput
from ray_tpu.util import xprof as _xprof


def _require_tpu(mode: str) -> float:
    """A mode that reports a device metric runs on the chip or not at
    all: no CPU stand-in under a device metric's name.  Returns the
    chip's peak bf16 FLOP/s from the device_kind table (an unknown
    device raises there)."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"bench.py {mode} reports a device metric and found no TPU "
            f"(platform {dev.platform!r}, {dev.device_kind!r})")
    return _xprof.resolve_peak_flops(dev.device_kind)


def _gpt2_bench_setup():
    """Shared model/optimizer setup for the GPT-2 benches: GPT-2 small
    (124M).  Returns (cfg, state, optimizer, loss_fn, one_step)."""
    from ray_tpu.models.gpt2 import (GPT2Config, gpt2_init, gpt2_loss_fn)
    from ray_tpu.train.train_step import (TrainState, make_optimizer,
                                          make_train_step)

    cfg = GPT2Config(n_layer=12, n_head=12, d_model=768, d_ff=3072,
                     vocab_size=50257, max_seq=1024, remat=True,
                     attn_impl="flash")

    params = gpt2_init(cfg, jax.random.PRNGKey(0))
    optimizer = make_optimizer(total_steps=1000)
    state = jax.device_put(TrainState.create(params, optimizer))

    def loss_fn(p, b):
        # 256-wide fused chunked xent (models/gpt2.py _chunked_xent
        # custom_vjp): measured best on-chip — the whole-logits path
        # pays ~3.3 GB of fp32 logits traffic per direction.
        return gpt2_loss_fn(cfg, p, b, loss_chunk=256)

    return cfg, state, optimizer, loss_fn, \
        make_train_step(loss_fn, optimizer)


def main() -> None:
    peak = _require_tpu("(default mode)")
    cfg, state, optimizer, loss_fn, one_step = _gpt2_bench_setup()
    batch, steps, reps = 16, 20, 3
    tokens = jax.random.randint(jax.random.PRNGKey(1),
                                (batch, cfg.max_seq + 1), 0,
                                cfg.vocab_size, jnp.int32)

    # The measured loop runs INSIDE one jit (lax.scan over steps): a
    # host-free training loop, synced by fetching the scalar loss.
    def run(state, tokens, n):
        def body(s, _):
            s, m = one_step(s, {"tokens": tokens})
            return s, m["loss"]
        state, losses = jax.lax.scan(body, state, None, length=n)
        return state, losses[-1]

    runner = jax.jit(run, static_argnums=(2,))
    ledger = _goodput.reset()
    # Warm up with the SAME step count (static arg => per-n executable;
    # timing a fresh n would measure compilation, not training).
    with ledger.phase("compile"):
        _, loss = runner(state, tokens, steps)
        _ = jax.device_get(loss)

    best = 0.0
    for _ in range(reps):
        t0 = time.perf_counter()
        with ledger.phase("compute"):
            _, loss = runner(state, tokens, steps)
            _ = jax.device_get(loss)
        elapsed = time.perf_counter() - t0
        best = max(best, batch * cfg.max_seq * steps / elapsed)

    tok_s = best
    flops_per_token = cfg.flops_per_token()
    mfu = tok_s * flops_per_token / peak
    # Telemetry-plane smoke check: a bench run must emit a non-empty
    # goodput summary whose fractions sum to ~1.0, so the goodput
    # ledger can't silently rot (it has no other standalone exercise).
    # Explicit raise, not assert — must survive `python -O`.
    gp = ledger.snapshot()
    fracs = ledger.fractions()
    if gp["seconds"].get("compute", 0.0) <= 0.0 \
            or gp["seconds"].get("compile", 0.0) <= 0.0:
        raise RuntimeError(
            f"empty goodput summary from bench run: {gp}")
    if abs(sum(fracs.values()) - 1.0) >= 1e-6:
        raise RuntimeError(f"goodput fractions don't normalize: {fracs}")
    # Automated step decomposition (util/xprof): forward / backward /
    # optimizer seconds via state-carried scans — the measurement
    # MFU_ANALYSIS.md performs by hand, now a bench output every run.
    decomp = _xprof.measure_step_decomposition(
        loss_fn, optimizer, state, {"tokens": tokens},
        steps=steps, reps=reps,
        flops_per_step=batch * cfg.max_seq * flops_per_token)
    decomp_out = {
        "forward_s": round(decomp["forward_s"], 6),
        "backward_s": round(decomp["backward_s"], 6),
        "optimizer_s": round(decomp["optimizer_s"], 6),
        "full_step_s": round(decomp["full_step_s"], 6),
        "shares": {k: round(v, 4)
                   for k, v in decomp["shares"].items()},
    }
    if "of_peak" in decomp:
        decomp_out["of_peak"] = {k: round(v, 4)
                                 for k, v in decomp["of_peak"].items()}
    out = {
        "metric": "gpt2_124m_pretrain_tokens_per_sec_per_chip",
        "value": round(tok_s, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.35, 4),
        "goodput": {p: round(f, 4) for p, f in fracs.items()},
        "decomposition": decomp_out,
    }
    print(json.dumps(out))
    # The decomposition row rides along under --record: optimizer
    # share is the "optimizer is ~free" MFU_ANALYSIS claim as a
    # regression-guarded number (lower is better — a growing share
    # means the update stopped overlapping/fusing).
    _maybe_record(out, extra_rows=[
        {"benchmark": "gpt2_step_optimizer_share",
         "value": round(decomp["shares"]["optimizer"], 4),
         "unit": "fraction", "higher_is_better": False}])


def data_pipeline() -> None:
    """--data-pipeline: GPT-2 pretraining fed END-TO-END from a
    ray_tpu.data pipeline — block tasks generate/prepare token batches
    through the cluster runtime, ``iter_batches`` assembles them by
    column slicing with ``prefetch_blocks`` pulling ahead, and
    ``train.iter_device_batches`` overlaps ``jax.device_put`` of batch
    N+1 with step N.  Reports tokens/s plus the ``data_stall`` goodput
    share, against an UNPIPELINED baseline (same dataset, synchronous
    batch fetch + inline device_put) measured in the same run — the
    end-to-end proof that the input path feeds the train step with
    ~zero stall (north-star risk: host-side data plane eating MFU).
    """
    import numpy as np

    import ray_tpu
    from ray_tpu import data as rt_data
    from ray_tpu import train as rt_train

    _require_tpu("--data-pipeline")
    cfg, state, optimizer, _loss_fn, step_fn = _gpt2_bench_setup()
    batch, steps, n_blocks = 16, 20, 8
    one_step = jax.jit(step_fn)
    rows_per_block = batch * steps // n_blocks
    seq = cfg.max_seq
    vocab = cfg.vocab_size

    def make_source(i):
        def src():
            rng = np.random.default_rng(1000 + i)
            return {"tokens": rng.integers(
                0, vocab, (rows_per_block, seq + 1), dtype=np.int64
            ).astype(np.int32)}
        return src

    owns = not ray_tpu.is_initialized()
    if owns:
        ray_tpu.init(mode="cluster", num_cpus=2)
    try:
        ds = rt_data.Dataset([make_source(i) for i in range(n_blocks)])

        ledger = _goodput.reset()
        warm = {"tokens": np.zeros((batch, seq + 1), np.int32)}
        with ledger.phase("compile"):
            s2, m = one_step(state, jax.device_put(warm))
            _ = jax.device_get(m["loss"])
        # Warm the CLUSTER too: one full untimed pass spawns workers,
        # ships the block-task code, and warms imports — otherwise the
        # first measured epoch (the unpipelined baseline) absorbs all
        # cold-start cost and the A/B comparison flatters the pipeline.
        for _ in ds.iter_batches(batch_size=batch, prefetch_blocks=0):
            pass

        def run_epoch(batches, *, inline_device_put: bool):
            """One pass over the dataset; returns (tokens/s, stall
            share of wall).  The final device_get inside the compute
            phase drains the async dispatch queue, so wall covers the
            real work."""
            st = state
            t0 = time.perf_counter()
            lg = _goodput.reset()
            n = 0
            it = iter(batches)
            last = None
            while True:
                if inline_device_put:
                    # Unpipelined baseline: the step loop itself waits
                    # for batch assembly + pays H2D inline.
                    try:
                        with rt_train.data_wait():
                            b = next(it)
                        b = jax.device_put(b)
                    except StopIteration:
                        break
                else:
                    try:
                        b = next(it)  # device batch; waits charged
                    except StopIteration:  # inside iter_device_batches
                        break
                with lg.phase("compute"):
                    st, last = one_step(st, b)
                n += 1
            with lg.phase("compute"):
                if last is not None:
                    _ = jax.device_get(last["loss"])
            wall = time.perf_counter() - t0
            stall = lg.snapshot()["seconds"].get("data_stall", 0.0)
            return (n * batch * seq / wall, stall / max(wall, 1e-9),
                    n)

        # Unpipelined baseline: synchronous fetch, no prefetch.
        base_tok_s, base_stall, n1 = run_epoch(
            ds.iter_batches(batch_size=batch, batch_format="numpy",
                            drop_last=True, prefetch_blocks=0),
            inline_device_put=True)
        # Zero-stall path: block prefetch + device prefetch.
        pipe_tok_s, pipe_stall, n2 = run_epoch(
            rt_train.iter_device_batches(
                ds.iter_batches(batch_size=batch,
                                batch_format="numpy",
                                drop_last=True, prefetch_blocks=2),
                depth=2),
            inline_device_put=False)
        if n1 != steps or n2 != steps:
            raise RuntimeError(
                f"pipeline delivered {n1}/{n2} batches, expected "
                f"{steps} — batching/split regression")
    finally:
        if owns:
            ray_tpu.shutdown()

    out = {
        "metric": "gpt2_data_pipeline_tokens_per_sec",
        "value": round(pipe_tok_s, 1),
        "unit": "tokens/s",
        # Pipelined throughput vs the unpipelined baseline of the SAME
        # run: >1.0 means the ingest pipeline pays for itself.
        "vs_baseline": round(pipe_tok_s / max(base_tok_s, 1e-9), 4),
        "extra": {
            "unpipelined_tokens_per_sec": round(base_tok_s, 1),
            "data_stall_share": round(pipe_stall, 4),
            "data_stall_share_unpipelined": round(base_stall, 4),
        },
    }
    print(json.dumps(out))
    _maybe_record(out, extra_rows=[
        {"benchmark": "data_pipeline_stall_share",
         "value": out["extra"]["data_stall_share"],
         "unit": "fraction", "higher_is_better": False}])


def long_context() -> None:
    """--long-context: ring attention (flash-fused, seq>=8k) vs the
    dense single-chip flash kernel (round-2 VERDICT item 3 'done' bar:
    ring within ~20% of dense flash).  vs_baseline = ring tokens/s /
    dense-flash tokens/s; one chip hosts the whole ring (n=1) — on a
    pod the seq axis spans chips and the ppermute rides ICI.
    """
    import functools

    import numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from ray_tpu.ops.flash_attention import flash_attention
    from ray_tpu.parallel.ring_attention import ring_attention

    peak = _require_tpu("--long-context")
    dev = jax.devices()
    b, h, t, d = 2, 12, 8192, 64
    steps, reps = 8, 3

    key = jax.random.PRNGKey(0)
    qkv = jax.random.normal(key, (3, b, t, h, d), jnp.bfloat16)

    mesh = Mesh(np.array(dev), ("seq",))
    spec = P(None, "seq", None, None)
    ring = shard_map(functools.partial(ring_attention, causal=True),
                     mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)

    def bench_fn(attn):
        def loss(q, k, v):
            return jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2)

        grad = jax.grad(loss, argnums=(0, 1, 2))

        def run(q, k, v, n):
            def body(c, _):
                g = grad(q + c, k, v)
                return c + g[0][0, 0, 0, 0].astype(jnp.bfloat16), None
            c, _ = jax.lax.scan(body, jnp.bfloat16(0.0), None, length=n)
            return c

        runner = jax.jit(run, static_argnums=(3,))
        q, k, v = qkv
        _ = jax.device_get(runner(q, k, v, steps))  # warm-up/compile
        best = 0.0
        for _ in range(reps):
            t0 = time.perf_counter()
            _ = jax.device_get(runner(q, k, v, steps))
            el = time.perf_counter() - t0
            best = max(best, b * t * steps / el)
        return best

    dense_tok_s = bench_fn(
        lambda q, k, v: flash_attention(q, k, v, causal=True))
    ring_tok_s = bench_fn(ring)
    # The ring mesh spans every local device while the dense baseline
    # jits onto one chip, so compare PER-CHIP throughput (and per-chip
    # MFU) — on an n-chip host the raw ring number is ~n× inflated.
    ring_tok_s_chip = ring_tok_s / len(dev)

    # Causal fwd+bwd attention FLOPs per token (QK^T + PV, backward
    # ~2.5x forward, causal halves the visible area).
    flops_tok = 3.5 * (4 * h * t * d) * 0.5
    mfu = ring_tok_s_chip * flops_tok / peak
    out = {
        "metric": f"ring_attention_seq{t}_tokens_per_sec_per_chip",
        "value": round(ring_tok_s_chip, 1),
        "unit": "tokens/s",
        "vs_baseline": round(ring_tok_s_chip / dense_tok_s, 4),
        "extra": {"dense_flash_tokens_per_sec": round(dense_tok_s, 1),
                  "ring_devices": len(dev),
                  "ring_attention_mfu": round(mfu, 4)},
    }
    print(json.dumps(out))
    _maybe_record(out)


def cold_start() -> None:
    """--cold-start: 100-replica serve deployment cold start through
    the control-plane fast path — the warm-worker prestart pool is
    filled FIRST, then the wall time from ``serve.run`` to every
    replica answering is measured.  Reports the adoption vs cold-spawn
    delta alongside (a nonzero fallback count means the pool was
    outrun and some replicas paid a full interpreter spawn).
    """
    import os
    import sys

    n_replicas = 10 if "--quick" in sys.argv else 100
    # Pool sizing must precede init so the agent's config carries it
    # (+ headroom for the serve controller/proxy actors).
    os.environ.setdefault("RT_WORKER_PRESTART", str(n_replicas + 8))
    os.environ.setdefault("RT_WORKER_POOL_MAX_WORKERS",
                          str(n_replicas + 64))
    os.environ.setdefault("RT_WORKER_PRESTART_BURST", "16")
    os.environ.setdefault("RT_ACTOR_READY_TIMEOUT_S", "600")

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.util.scale_bench import _pool_totals, wait_pool_fill

    ray_tpu.init(mode="cluster", num_cpus=4)
    try:
        filled = wait_pool_fill(n_replicas + 4, timeout=600.0)
        print(f"prestart pool warm: {filled} idle worker(s)",
              flush=True)
        before = _pool_totals()

        @serve.deployment(num_replicas=n_replicas, name="cold",
                          ray_actor_options={"num_cpus": 0})
        def noop(_req=None):
            return "ok"

        t0 = time.perf_counter()
        serve.run(noop.bind(), route_prefix="/cold")
        # "Cold start" ends when every replica process answers — poll
        # each replica actor directly (the handle would be satisfied
        # by the first few live replicas).
        ctl = ray_tpu.get_actor(serve.CONTROLLER_NAME)
        replicas = ray_tpu.get(ctl.get_replicas.remote("cold"),
                               timeout=120)
        ray_tpu.get([r.ongoing.remote() for r in replicas],
                    timeout=600)
        dt = time.perf_counter() - t0
        after = _pool_totals()
        adopted = int(after["adoptions"] - before["adoptions"])
        cold = int(after["cold_spawns"] - before["cold_spawns"])
        out = {
            "metric": f"serve_cold_start_{n_replicas}_replicas_s",
            "value": round(dt, 3), "unit": "s",
            "extra": {"replicas": len(replicas), "adopted": adopted,
                      "cold_spawn_fallbacks": cold},
        }
        print(json.dumps(out))
        if len(replicas) != n_replicas:
            raise RuntimeError(
                f"cold start brought up {len(replicas)} of "
                f"{n_replicas} replicas")
        _maybe_record(out, higher_is_better=False)
    finally:
        ray_tpu.shutdown()


def serve_llm() -> None:
    """--serve-llm: load-test the LLM inference plane at saturating
    concurrency — a tiny GPT-2 ``LLMDeployment`` (continuous-batching
    engine + paged KV cache) behind serve, token streams pulled by
    concurrent clients through ``handle.stream``.  Reports p50/p99
    time-to-first-token and aggregate generated tokens/s, plus honest
    decode MFU via ``decode_flops_per_token`` (the 6ND training count
    would overstate it 3x).  ``--record`` appends
    serve_llm_tokens_per_sec (floored in PERF.jsonl) and the TTFT
    percentiles."""
    import sys
    import threading

    import numpy as np

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm import EngineConfig, llm_deployment
    from ray_tpu.models.gpt2 import GPT2Config

    peak = _require_tpu("--serve-llm")
    quick = "--quick" in sys.argv
    cfg = GPT2Config(n_layer=12, n_head=12, d_model=768, d_ff=3072,
                     vocab_size=50257, max_seq=1024, remat=False,
                     attn_impl="dense")
    engine_cfg = EngineConfig(page_size=16, num_pages=256, max_batch=8,
                              prefill_token_budget=512)
    concurrency = 8                      # = max_batch: saturates the
    per_client = 1 if quick else 4       # continuous batch
    prompt_len, max_tokens = 16, 32
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, prompt_len).tolist()
               for _ in range(concurrency * per_client)]

    ray_tpu.init(mode="cluster", num_cpus=4)
    try:
        handle = serve.run(
            llm_deployment(name="llm", model="gpt2", model_cfg=cfg,
                           engine_cfg=engine_cfg),
            route_prefix="/llm")
        # Warm the full path (replica __init__ already compiled the
        # engine; this warms the handle/stream plumbing and the
        # pad-16 prefill shape).  "warmup" keeps its compile-laden
        # prefill out of the engine's TTFT/TPOT accounting.
        _ = [f for f in handle.stream(
            {"prompt": prompts[0], "max_tokens": 4,
             "warmup": True})]

        ttfts, counts, errors, tpots = [], [], [], []
        lock = threading.Lock()

        def client(idx: int) -> None:
            from ray_tpu.util import tracing

            for r in range(per_client):
                payload = {"prompt": prompts[idx * per_client + r],
                           "max_tokens": max_tokens}
                t0 = time.perf_counter()
                first, n, prev = None, 0, None
                gaps = []
                try:
                    # request_id on: the run measures throughput WITH
                    # request tracing active (waiting/prefill/decode
                    # spans + TPOT), so the recorded tokens/s floor
                    # bounds the tracing overhead.
                    for fr in handle.stream(
                            payload,
                            request_id=tracing.new_request_id()):
                        if "error" in fr:
                            raise RuntimeError(fr["error"])
                        if "token" in fr:
                            now = time.perf_counter()
                            if first is None:
                                first = now - t0
                            elif prev is not None:
                                gaps.append(now - prev)
                            prev = now
                            n += 1
                except Exception as e:  # noqa: BLE001
                    with lock:
                        errors.append(repr(e))
                    continue
                with lock:
                    ttfts.append(first)
                    counts.append(n)
                    tpots.extend(gaps)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(concurrency)]
        t0 = time.perf_counter()
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        wall = time.perf_counter() - t0
        if errors:
            raise RuntimeError(
                f"{len(errors)} request(s) failed: {errors[:3]}")
        stats = ray_tpu.get(handle.method("stats").remote(), timeout=30)
    finally:
        ray_tpu.shutdown()

    tok_s = sum(counts) / wall
    ttft_ms = np.asarray(sorted(ttfts)) * 1e3
    p50 = float(np.percentile(ttft_ms, 50))
    p99 = float(np.percentile(ttft_ms, 99))
    tpot_ms = np.asarray(sorted(tpots)) * 1e3 if tpots else \
        np.asarray([0.0])
    tpot_p50 = float(np.percentile(tpot_ms, 50))
    tpot_p99 = float(np.percentile(tpot_ms, 99))
    # TTFT phase decomposition from the engine's own accounting:
    # where the mean first token actually waited.
    n_req = max(stats.get("ttft_requests", 0), 1)
    wait_ms = 1e3 * stats.get("ttft_waiting_s_total", 0.0) / n_req
    prefill_ms = 1e3 * stats.get("ttft_prefill_s_total", 0.0) / n_req
    print(f"ttft decomposition (engine means over "
          f"{stats.get('ttft_requests', 0)} request(s)): "
          f"engine_waiting {wait_ms:.1f}ms + prefill "
          f"{prefill_ms:.1f}ms of ttft p50 {p50:.1f}ms; "
          f"tpot p50 {tpot_p50:.2f}ms p99 {tpot_p99:.2f}ms")
    mfu = (tok_s * cfg.decode_flops_per_token(prompt_len + max_tokens // 2)
           / peak)
    out = {
        "metric": "serve_llm_tokens_per_sec",
        "value": round(tok_s, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu, 4),   # decode MFU
        "extra": {
            "ttft_p50_ms": round(p50, 1),
            "ttft_p99_ms": round(p99, 1),
            "tpot_p50_ms": round(tpot_p50, 2),
            "tpot_p99_ms": round(tpot_p99, 2),
            "ttft_engine_waiting_mean_ms": round(wait_ms, 2),
            "ttft_prefill_mean_ms": round(prefill_ms, 2),
            "requests": len(counts),
            "concurrency": concurrency,
            "kv_pages_used_after": stats["kv_pages_used"],
            "engine_steps": stats["steps"],
            "evictions": stats["evictions"],
        },
    }
    print(json.dumps(out))
    _maybe_record(out, extra_rows=[
        {"benchmark": "serve_llm_ttft_p50_ms", "value": round(p50, 1),
         "unit": "ms", "higher_is_better": False},
        {"benchmark": "serve_llm_ttft_p99_ms", "value": round(p99, 1),
         "unit": "ms", "higher_is_better": False},
        {"benchmark": "serve_llm_tpot_p99_ms",
         "value": round(tpot_p99, 2),
         "unit": "ms", "higher_is_better": False}])


def fsdp() -> None:
    """--fsdp: GPT-2 sharded train steps over a 2-process CPU mesh.

    The multi-host training plane's standing bench: two member
    processes (each with 2 virtual CPU devices) rendezvous through
    jax.distributed, lay the 4 devices out as a process-contiguous
    fsdp x tensor gang mesh (train.distributed), shard the TrainState
    by the GPT-2 partition rules, and run jit-with-shardings train
    steps whose gradient reductions cross the process boundary (gloo).
    Records ``train_fsdp_tokens_per_sec`` (global tokens through the
    sharded step, a floor against GSPMD-path regressions — extra
    resharding copies, lost donation) plus per-mesh-axis collective
    byte shares harvested by util/xprof from the timed executable's
    post-SPMD HLO.  This mode is a CPU gang by construction (it says so
    in its ``platform`` field and reports no MFU); the same step on four
    real chips is ``python chip_smoke.py --four-chip``."""
    import os
    import socket
    import subprocess
    import sys

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    addr = f"127.0.0.1:{s.getsockname()[1]}"
    s.close()
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    procs = [subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--fsdp-member",
         str(rank), addr], env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True) for rank in range(2)]
    outs = [p.communicate(timeout=600)[0] for p in procs]
    for rank, (p, o) in enumerate(zip(procs, outs)):
        if p.returncode != 0:
            raise RuntimeError(
                f"fsdp bench member {rank} failed:\n{o[-3000:]}")
    member = None
    for line in outs[0].splitlines():
        if line.startswith("FSDP-MEMBER-0 "):
            member = json.loads(line.split(" ", 1)[1])
    if member is None:
        raise RuntimeError(
            f"fsdp bench member 0 printed no result:\n{outs[0][-3000:]}")
    out = {
        "metric": "train_fsdp_tokens_per_sec",
        "value": round(member["tokens_per_sec"], 1),
        "unit": "tokens/s",
        "vs_baseline": 0.0,  # CPU mesh: MFU vs 35% is not meaningful
        "mesh": member["mesh"],
        "world": 2,
        "compile_s": round(member["compile_s"], 2),
        "platform": member.get("platform", "cpu"),
        "collective_bytes": member.get("collective_bytes", 0.0),
        "axis_shares": member.get("axis_shares", {}),
    }
    print(json.dumps(out))
    # Axis byte shares are static facts of the compiled program; a
    # rising fsdp/tensor share means the partitioner started moving
    # more bytes over that axis per step (lower is better).
    rows = [
        {"benchmark": f"train_fsdp_collective_share_{axis}",
         "value": share, "unit": "fraction", "higher_is_better": False}
        for axis, share in sorted(member.get("axis_shares",
                                             {}).items())]
    _maybe_record(out, extra_rows=rows)


def _fsdp_member(rank: int, addr: str) -> None:
    """One rank of the --fsdp bench (spawned by ``fsdp`` above)."""
    import os
    import time as _time

    import numpy as np

    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=addr,
                               num_processes=2, process_id=rank)
    from jax.sharding import NamedSharding, PartitionSpec

    from ray_tpu.models.gpt2 import (GPT2Config, gpt2_init,
                                     gpt2_loss_fn)
    from ray_tpu.parallel.mesh import gang_mesh
    from ray_tpu.parallel.partition_rules import tree_shardings
    from ray_tpu.train import distributed as dist
    from ray_tpu.train.train_step import (TrainState, make_optimizer,
                                          make_sharded_train_step)

    cfg = GPT2Config(vocab_size=2048, n_layer=4, n_head=8, d_model=256,
                     d_ff=1024, max_seq=256, remat=True)
    params = gpt2_init(cfg, jax.random.PRNGKey(0))
    optimizer = make_optimizer(total_steps=1000)
    state = TrainState.create(params, optimizer)
    shape = dist.derive_mesh_shape(2, jax.local_device_count())
    mesh = gang_mesh(shape)
    state, specs = dist.shard_train_state(
        state, mesh, dist.rules_for_model("gpt2"))
    shardings = tree_shardings(mesh, specs)
    # telemetry=True: the step compiles through the AOT path, so the
    # xprof plane harvests the post-SPMD HLO — per-axis collective
    # bytes come from the SAME executable the bench times.
    step = make_sharded_train_step(
        lambda p, b: gpt2_loss_fn(cfg, p, b, loss_chunk=0), optimizer,
        mesh=mesh, state_shardings=shardings,
        batch_sharding=NamedSharding(mesh, PartitionSpec("fsdp")),
        telemetry=True)
    gbs, steps = 8, 6
    rng = np.random.default_rng(0)
    full = rng.integers(0, cfg.vocab_size,
                        (gbs, cfg.max_seq + 1)).astype(np.int32)
    lo, hi = dist.global_batch_slice(gbs, shape, rank, 2)
    batch = dist.put_global_batch({"tokens": full[lo:hi]}, mesh,
                                  global_batch_size=gbs)
    t0 = _time.perf_counter()
    state, metrics = step(state, batch)
    _ = dist.metrics_to_host(metrics)
    compile_s = _time.perf_counter() - t0
    t0 = _time.perf_counter()
    for _i in range(steps):
        state, metrics = step(state, batch)
    _ = dist.metrics_to_host(metrics)  # sync the async dispatch tail
    elapsed = _time.perf_counter() - t0
    tok_s = gbs * cfg.max_seq * steps / elapsed
    # Per-axis collective byte shares from the xprof plane: static
    # post-SPMD HLO facts of the timed executable (deterministic per
    # compile — unlike timing, safe to regression-guard).
    from ray_tpu.util import xprof

    colls = (xprof.local_programs().get("train_step") or {}).get(
        "collectives") or {}
    total_cbytes = sum(a.get("bytes", 0.0) for a in colls.values())
    axis_shares = {
        axis: round(a.get("bytes", 0.0) / total_cbytes, 4)
        for axis, a in colls.items()} if total_cbytes > 0 else {}
    if rank == 0:
        print("FSDP-MEMBER-0 " + json.dumps(
            {"tokens_per_sec": tok_s, "compile_s": compile_s,
             "mesh": shape,
             "platform": jax.devices()[0].platform,
             "collective_bytes": total_cbytes,
             "axis_shares": axis_shares,
             "loss": dist.metrics_to_host(metrics)["loss"]}),
            flush=True)


def _maybe_record(out: dict, extra_rows: list = None,
                  higher_is_better: bool = True) -> None:
    """--record: append to the PERF.jsonl round-over-round regression
    ledger (tests/test_perf_ledger.py guards >20% drops)."""
    import sys

    if "--record" not in sys.argv:
        return
    from ray_tpu.util import perf_ledger

    perf_ledger.record(
        [{"benchmark": out["metric"], "value": out["value"],
          "unit": out["unit"],
          "higher_is_better": higher_is_better}]
        + list(extra_rows or []),
        source="bench")


if __name__ == "__main__":
    import sys

    _compile_cache.apply()
    if "--long-context" in sys.argv:
        long_context()
    elif "--data-pipeline" in sys.argv:
        data_pipeline()
    elif "--cold-start" in sys.argv:
        cold_start()
    elif "--serve-llm" in sys.argv:
        serve_llm()
    elif "--fsdp-member" in sys.argv:
        i = sys.argv.index("--fsdp-member")
        _fsdp_member(int(sys.argv[i + 1]), sys.argv[i + 2])
    elif "--fsdp" in sys.argv:
        fsdp()
    else:
        main()
