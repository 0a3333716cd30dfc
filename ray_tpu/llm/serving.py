"""LLM serving — the generation engine deployed through ``serve``.

``LLMDeployment`` hosts ONE GenerationEngine per replica; its
``__call__`` is a generator, so serve routes it through the existing
streaming plane end to end: tokens ride the core ObjectRefGenerator
path, the HTTP/gRPC proxies deliver them as chunked ndjson / gRPC
streams, and PR-8's resilience semantics apply unchanged (pre-first-
token failures retry on another replica, mid-stream faults surface as
the typed StreamInterruptedError / ``__rt_stream_error__`` terminal
frame — never silent truncation).

Scaling and lifecycle reuse the serve planes as-is: a live stream
counts as an ongoing request, so the request autoscaler sees engine
load + admission-queue depth directly; ``max_ongoing_requests``
defaults to the engine's continuous-batch capacity so overload queues
(and sheds) at the handle instead of overcommitting a replica; and
replicas on DRAINING nodes bleed off through the serve controller's
existing drain path.  A client that disconnects mid-stream triggers
the generator's ``finally``, which cancels the sequence and frees its
KV pages (the eviction path, pinned by tests).
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

from ..util import compile_cache
from .engine import EngineConfig, GenerationEngine
from .sampling import SamplingParams


class LLMDeployment:
    """Serve deployment class: one engine per replica, streaming
    token frames per request.

    Request payload (JSON-able dict):
      {"prompt": [token ids], "max_tokens": int?, "temperature": f?,
       "top_k": int?, "top_p": f?, "seed": int?}
    ``seed`` is any int: a sampled request's tokens depend on it and on
    each token's index alone (the device sampler's key), whatever else
    is in the batch; without one the engine gives each request its own.
    Response frames: {"token": id, "index": i} per token, then
      {"done": true, "reason": "eos"|"length", "n_tokens": n}
    (or {"error": "..."} for a rejected/failed request).
    """

    def __init__(self, model: str = "gpt2", model_cfg: Any = None,
                 engine_cfg: Optional[EngineConfig] = None,
                 seed: int = 0, warmup: bool = True):
        import threading

        # Engine construction (jax import, weight init, prefill/decode
        # compiles) can take tens of seconds — far past the serve
        # controller's health-probe deadline, which would kill and
        # replace a replica still in __init__ forever.  So __init__
        # returns immediately (the actor answers health probes) and a
        # background thread builds + warms the engine; requests block
        # on readiness.
        self._ready = threading.Event()
        # This replica process's persistent compile-cache hits/misses.
        self._cache_counts = compile_cache.watch()
        self._init_error: Optional[BaseException] = None
        self._engine: Optional[GenerationEngine] = None

        def _build() -> None:
            try:
                engine = GenerationEngine(
                    model=model, model_cfg=model_cfg,
                    engine_cfg=engine_cfg, seed=seed).start()
                if warmup:
                    # Pay compiles now, not on the first request's
                    # TTFT.
                    engine.warmup()
                self._engine = engine
            except Exception as e:  # noqa: BLE001 — surfaced per call
                # ...and by check_health(): a replica whose engine
                # never came up is replaced, not kept routable.
                self._init_error = e
            finally:
                self._ready.set()

        threading.Thread(target=_build, daemon=True,
                         name="llm-engine-init").start()

    def _engine_or_raise(self, timeout_s: float = 600.0
                         ) -> GenerationEngine:
        if not self._ready.wait(timeout_s):
            raise RuntimeError("LLM engine initialization timed out")
        if self._init_error is not None:
            raise RuntimeError(
                "LLM engine failed to initialize: "
                f"{self._init_error!r}") from self._init_error
        return self._engine

    def check_health(self) -> None:
        """Serve's health probe: raises once engine initialisation has
        failed (still building is healthy — it answers probes)."""
        if self._init_error is not None:
            self._engine_or_raise(0)

    def __call__(self, payload: Optional[Dict[str, Any]]):
        engine = self._engine_or_raise()
        payload = payload or {}
        # Request tracing: the ingress-minted id arrives through the
        # injected span context (the replica adopts it around task
        # execution); handing it to the engine opts this sequence into
        # waiting/prefill/decode lifecycle spans for `rt trace <id>`.
        from ..util import tracing

        rid = tracing.current_request_id()
        try:
            prompt = [int(t) for t in payload["prompt"]]
            params = SamplingParams(
                temperature=float(payload.get("temperature", 0.0)),
                top_k=int(payload.get("top_k", 0)),
                top_p=float(payload.get("top_p", 1.0)))
            seq = engine.submit(
                prompt,
                max_tokens=payload.get("max_tokens"),
                params=params,
                seed=payload.get("seed"),
                request_id=rid,
                # {"warmup": true} opts a request out of the TTFT/
                # TPOT accounting (clients priming compile shapes —
                # e.g. bench's handle-path warm call — must not skew
                # the decomposition real traffic is judged by).
                _warmup=bool(payload.get("warmup")))
        except (KeyError, TypeError, ValueError) as e:
            yield {"error": f"bad request: {e!r}"}
            return
        try:
            for frame in engine.frames(seq):
                yield frame
        finally:
            # Client gone (GeneratorExit) or stream complete: cancel is
            # a no-op on finished sequences, and the eviction path for
            # disconnects — pages freed, sequence out of the batch.
            engine.cancel(seq.sid)

    def stats(self) -> Dict[str, Any]:
        """The engine's stats, plus this replica process's persistent
        compile-cache hits and misses."""
        out = self._engine_or_raise().stats()
        out["compile_cache"] = {
            "dir": os.environ.get(compile_cache.ENV),
            **self._cache_counts}
        return out


def llm_deployment(name: str = "llm", model: str = "gpt2",
                   model_cfg: Any = None,
                   engine_cfg: Optional[EngineConfig] = None,
                   num_replicas: int = 1,
                   autoscaling: Any = None,
                   max_ongoing_requests: Optional[int] = None,
                   num_cpus: float = 1, seed: int = 0,
                   warmup: bool = True,
                   route_prefix: Optional[str] = None):
    """Build the serve Application for an LLM deployment.

    ``autoscaling`` takes a serve.AutoscalingConfig: replica count then
    follows engine load — streams in flight plus handle queue depth —
    through the existing request autoscaler.  ``max_ongoing_requests``
    defaults to the engine's max_batch so admission control saturates
    exactly when the continuous batch does.

    Each replica LEASES the chip it computes on, like any other TPU
    actor, so the scheduler never places other chip work on it: one
    chip where the running cluster has chips, none on a CPU cluster.
    """
    from .. import serve
    from ..util.chips import max_node_chips

    engine_cfg = engine_cfg or EngineConfig()
    if max_ongoing_requests is None:
        max_ongoing_requests = engine_cfg.max_batch
    actor_options: Dict[str, Any] = {"num_cpus": num_cpus}
    if max_node_chips() > 0:
        actor_options["num_tpus"] = 1
    dep = serve.deployment(
        LLMDeployment, name=name, num_replicas=num_replicas,
        ray_actor_options=actor_options,
        autoscaling_config=autoscaling,
        route_prefix=route_prefix,
        max_ongoing_requests=max_ongoing_requests)
    return dep.bind(model=model, model_cfg=model_cfg,
                    engine_cfg=engine_cfg, seed=seed, warmup=warmup)
