"""Continuous-batching generation engine (Orca-style iteration-level
scheduling over the paged KV cache).

One engine hosts one model replica and runs a step loop with NO batch
barriers: every step it (1) admits waiting sequences — each admission
is a prefill forward that populates the sequence's KV pages and samples
its first token — packing admissions under a per-step token budget so a
long prompt cannot starve running decodes, (2) runs ONE batched decode
forward over every running sequence (padded to the fixed ``max_batch``
shape so the jitted step compiles once), and (3) retires finished
sequences and frees their pages immediately.  A request submitted while
others are mid-generation starts decoding on the very next step — the
continuous-batching property the serving cells measure as TTFT under
load (pinned by tests/test_llm_engine.py).

The loop is a pipeline of depth one: the engine LAUNCHES a program (a
prefill, or a decode step) and only then reads the token ids of the
program launched before it, so the host's bookkeeping, its frame queues
and its next schedule run under the device's work and not between its
programs.  Nothing a launch needs comes from the host's copy of the ids:
a sequence keeps its row of the decode batch while it runs, each row's
latest id stays on the device (``sampling.py jit_feed`` puts a program's
ids into the ``[max_batch, 1]`` array the next decode step takes as its
tokens), and positions, pages and the sampler's key advance when a
program is LAUNCHED (``_Sequence.launched``, ``n_cached``); tokens,
frames, counters and retirement advance when its ids are DELIVERED
(``_Sequence.generated``).  A sequence that reaches ``max_tokens`` or
the context's end by its launched count is not launched again and gives
its row and pages back at once (the device runs programs in launch
order: whatever takes them next writes after it).  Only EOS and
cancellation are not known ahead: such a row was launched once more, and
that one result is dropped.  The pipeline drains (the ids are read
before anything is decided) for an eviction, on a step error, when the
batch is empty and at ``stop()``.  ``stats()["pipeline"]`` counts
``launched_ahead`` (programs launched while another's ids were unread),
``drains`` by cause (``evict``, ``error``, ``empty``, ``stop``) and
``rows_discarded`` (launched rows whose result was dropped).

Every program is accounted for where its ids are delivered
(``stats()["runs"]``, by the name ``stats()["programs"]`` knows it by:
``llm_decode``, ``llm_prefill[<bucket>]``): how many ran, over how many
rows and token positions, and what each cost the loop, ``paced_s``: from
the instant the program before it had its ids on the host, or from its
own launch where that was later, to the instant ITS ids were on the
host.  Device-bound that is the program's device time with its sampler
and feed; host-bound it is the host's pace.  The intervals tile the time
the pipeline was full; what lies between a delivery and a later launch
(a drain, ``llm.idle``) is nobody's, ``runs_unpaced_s``, and an interval
that holds a compile is in no program's sums (``runs_voided_s``).  The
same intervals by power-of-two milliseconds (``by_ms``, ``s_by_ms``)
show a stall, which a mean hides and a lifetime maximum cannot date.

Memory pressure is handled vLLM-style by recompute preemption: when a
running sequence needs a page and the pool is empty, the most recently
admitted OTHER sequence is evicted — pages freed, tokens kept — and
re-prefills (prompt + everything it already generated) when pages free
up, so already-streamed tokens are never re-emitted and greedy output
is unchanged.

What a sequence keeps on the device between steps (pages of a group of
layers, a ring, a slot), in which arrays, through which tables and from
which allocators, is ``kv_cache.py SequenceCache``'s and told there: the
engine takes, grows and gives back a sequence's holding through it,
hands its arrays to the one jitted forward and schedules.

Tokens are chosen ON THE DEVICE: after each forward one jitted sampler
(sampling.py ``jit_sampler``) takes the device-resident logits of the
last positions and, as data, every row's temperature / top-k / top-p,
seed words and token index, and the host fetches ``[max_batch]`` int32
ids, never a ``[max_batch, V]`` array.  One compiled sampler serves
every mix of requests; a request's tokens depend on its seed and the
token's index alone (not on its slot, its neighbours, or a recompute
preemption).  Tokens stream out through per-sequence queues; the serve
deployment (serving.py) turns them into streaming-generator frames.

Where a step's time goes is measured inside it, on two clocks that
agree: every phase of ``step()`` is a profiler annotation
(``spans.annotate``: ``llm.step`` > ``llm.admit`` > ``llm.prefill`` >
``llm.prefill.run`` ...), so a device capture shows what the host was
doing in each idle gap, and the leaves' ``perf_counter`` sums are in
``stats()["phase_s"]`` (PHASE_LEAVES; ``llm.other`` is the rest of
``step_s``, so the parts sum to the whole), with the engine thread's CPU
time beside them, read on every 16th step (``phase_cpu_s``,
``step_cpu_s``, ``cpu_sample``: ``spans.Phases``).
The ids of the program before are fetched AFTER the launch and inside
the ``llm.decode`` / ``llm.prefill`` annotation of that launch, so a
capture still finds each device run's start under the annotation that
launched it.  Nothing of this goes into the span ring: only the
per-request lifecycle spans do.
"""

from __future__ import annotations

import itertools
import queue
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import numpy as np

from ..util import chips
from ..util.spans import Phases, annotate
from .kv_cache import Holding, SequenceCache
from .sampling import (SamplingParams, jit_feed, jit_sampler, pack_rows,
                       seed_words)


@dataclass(frozen=True)
class EngineConfig:
    page_size: int = 16
    num_pages: int = 512
    max_batch: int = 8              # concurrent decoding sequences
    # Per-step token budget shared by prefill admissions (padded prompt
    # lengths) and the decode batch (1 token per running sequence).
    prefill_token_budget: int = 1024
    max_context: Optional[int] = None   # default: model max_seq
    eos_id: Optional[int] = None
    max_tokens_default: int = 64
    # Max gap between output frames before a consumer gives up on a
    # sequence (covers long recompute-preemption parks under KV
    # pressure; size it to worst-case pool contention).
    stream_idle_timeout_s: float = 300.0


# The leaf phases of one step(): each is an annotation of this name and a
# cumulative-seconds entry of stats()["phase_s"].  ``llm.admit`` is the
# admission's own time (lock, page allocation), its prefills apart.
# ``.run`` launches the forward, the sampler and the feed; ``.fetch`` waits
# for the token ids of the program launched BEFORE that one (of the
# program itself in a drain) and names it in its tags (``program``, and
# ``run``: how many of that program were delivered before it);
# ``.sample`` is the host's bookkeeping per token of the ids just fetched.
PHASE_LEAVES = (
    "llm.cancel", "llm.admit",
    "llm.prefill.pack", "llm.prefill.run", "llm.prefill.fetch",
    "llm.prefill.sample",
    "llm.decode.pages", "llm.decode.pack", "llm.decode.run",
    "llm.decode.fetch", "llm.decode.sample",
    "llm.publish")


def _bucket(n: int, floor: int = 8) -> int:
    """Pad prefill lengths to power-of-two buckets: bounded number of
    compiled prefill shapes instead of one per prompt length."""
    b = floor
    while b < n:
        b *= 2
    return b


class _Sequence:
    """One in-flight generation request (engine-internal)."""

    __slots__ = ("sid", "tokens", "prompt_len", "max_tokens", "params",
                 "seed", "out", "held", "n_cached", "launched",
                 "generated", "finished", "cancelled", "submitted_ts",
                 "request_id", "first_token_ts", "last_token_ts",
                 "warmup")

    def __init__(self, sid: int, prompt: List[int], max_tokens: int,
                 params: SamplingParams, seed: int,
                 request_id: Optional[str] = None,
                 warmup: bool = False):
        self.sid = sid
        self.tokens = list(prompt)      # prompt + generated so far
        self.prompt_len = len(prompt)
        self.max_tokens = max_tokens
        self.params = params
        self.seed = seed_words(seed)    # the sampler's key, two uint32
        self.out: "queue.Queue" = queue.Queue()
        # What it holds of the device's caches; ``held.slot`` is its row
        # while it runs: of the decode batch and of the device's token
        # array.
        self.held = Holding()
        # Advanced at LAUNCH: positions written into KV pages by the
        # programs launched so far, and the tokens those programs
        # sample (the next one's index: the sampler's key word).
        self.n_cached = 0
        self.launched = 0
        # Advanced at DELIVERY, with ``tokens`` and the frames.
        self.generated = 0
        self.finished = False
        self.cancelled = False
        self.submitted_ts = time.time()
        # Request tracing (minted at the serve ingress): lifecycle
        # spans — waiting-queue, prefill, decode — tag this id so
        # `rt trace <id>` shows where a request's TTFT went.
        self.request_id = request_id
        self.first_token_ts: Optional[float] = None
        self.last_token_ts: Optional[float] = None
        # Warmup sequences pay the prefill/decode COMPILES: their
        # multi-second samples must not enter the TTFT-phase/TPOT
        # accounting real traffic is judged by.
        self.warmup = warmup


class _Flight:
    """A launched program whose token ids the host has not read: the ids
    ``[max_batch]`` (and the program's routing counters) still on the
    device, and which sequence each of its rows is."""

    __slots__ = ("kind", "name", "ids", "moe", "residual", "rows",
                 "launched_at", "void", "admitted")

    def __init__(self, kind: str, name: str, ids, counters, rows,
                 launched_at: float, admitted: Optional[float] = None):
        self.kind = kind                # "decode" or "prefill"
        self.name = name                # ``_call_fwd``'s: the program
        self.ids = ids
        self.moe, self.residual = counters  # ``_call_fwd``'s
        self.rows: List[tuple] = rows   # (sequence, its row of ``ids``)
        # For stats()["runs"]: when its ``.run`` leaf began, and whether
        # a compile ran before its delivery.
        self.launched_at = launched_at
        self.void = False
        # When a FIRST admission's prefill began: its delivery is the
        # request's first token (TTFT's prefill phase ends there).
        self.admitted = admitted


# Of ops/moe.py ``MOE_COUNTERS`` (and ``layer_runs``), what each kind of
# run adds up: the decode runs' readers divide by decode runs, and no
# decode step takes the compact branch.
_MOE_KEPT = {"decode": ("layer_runs", "pairs", "experts_hit", "max_load"),
             "prefill": ("layer_runs", "pairs", "compact")}

# A decode row without a sequence, to the sampler: argmax.
_IDLE_ROW = (SamplingParams(), (0, 0), 0)

# stats()["runs"]: a program's intervals by power-of-two milliseconds,
# ``int(ms).bit_length()``: < 1, 1-2, 2-4, ..., 8192-16384, >= 16384.
RUN_BINS = 16


def _run_entry(name: str, runs: int, rows: int, by_ms: List[int],
               s_by_ms: List[float]) -> Dict[str, Any]:
    """A program's entry of stats()["runs"] from what ``_file`` keeps:
    ``tokens`` are the positions it computed (a prefill's bucket a run,
    as its name has it; a decode step's rows), ``paced_s`` the sum of
    its timed intervals."""
    bucket = name.partition("[")[2].rstrip("]")
    return {"runs": runs, "rows": rows,
            "tokens": runs * int(bucket) if bucket else rows,
            "paced_s": sum(s_by_ms), "by_ms": list(by_ms),
            "s_by_ms": list(s_by_ms)}


def jit_forward(model):
    """The engine's one jitted forward: it serves prefill ([1, bucket])
    and decode ([max_batch, 1]); XLA specializes per shape.  After the
    tokens it takes the paged pool's arrays, whole (llm/kv_cache.py
    ``pool_arrays``: ``k_pages`` and ``v_pages``, each [L, pages, page,
    h_kv*d], or for a model with latent attention the ONE array
    ``latent_pages`` [L, pages, page, row]); donated, and carried
    through the layers by the model, they are updated in place: the
    program scatters the new rows and holds no second pool
    (tests/test_llm.py and tests/test_tpu_compile.py pin that).  Then
    the page table and the positions.  A model with window layers takes
    the window group's ``window_k_pages`` and ``window_v_pages`` after
    ``v_pages`` (donated and aliased alike) and its ``window_table``
    after ``page_table``.  A model whose cache spec has
    recurrent layers takes, after the positions, the state pool's arrays
    (llm/kv_cache.py ``state_arrays``: ``conv`` and ``ssm``, or ``conv``
    alone; donated and updated in place as the pages are) and each row's
    slot ``[B]``.  Returns the logits, the paged pool's arrays, then the
    state pool's.  The logits are every position's, ``[B, T, V]`` float32,
    unless the caller says which position of each row it serves, by the
    keyword ``last`` (int32 ``[B]``, an index within ``T``): then the
    model cuts its hidden state to that position before its final norm
    and its head, and the logits are ``[B, 1, V]``; every cache is
    written as without it.  The engine passes it for a prefill (the
    prompt's last token) and not for a decode step; a caller that does
    not pass it (the positional call) gets every position.  A model
    with experts returns one more output, its
    routing counters ([layers with experts, 4] int32, ops/moe.py
    ``moe_counters``), and one whose config has a residual kind one after
    that: what its maps sowed (float32, models/decoder.py
    ``residual_counters``)."""
    import jax

    from ..models import family_of
    from ..models.decoder import residual_counters
    from ..ops.moe import moe_counters
    from .kv_cache import pool_arrays, pool_tables, state_arrays

    spec = family_of(model.cfg).cache(model.cfg)
    pools, held = pool_arrays(spec), state_arrays(spec)
    tables = pool_tables(spec)
    n = len(pools)

    def run(p, tokens, paged, page_tables, positions, state, last):
        cache = dict(zip(pools + tables, paged + page_tables, strict=True))
        if held:
            cache.update(zip(held + ("slots",), state, strict=True))
        (logits, new), sown = model.apply(
            p, tokens, kv_cache=cache, positions=positions, last=last,
            mutable=["intermediates"])
        out = (logits,) + tuple(new[name] for name in pools + held)
        sown = sown.get("intermediates", {})
        return out + tuple(c for c in (moe_counters(sown),
                                       residual_counters(sown))
                           if c is not None)

    # The parameters' names are part of the compiled program's text (and
    # of the compile cache's key): the K/V families keep theirs.
    if n == 4:
        def fwd(p, tokens, k_pages, v_pages, window_k_pages,
                window_v_pages, page_table, window_table, positions,
                *state, last=None):
            return run(p, tokens, (k_pages, v_pages, window_k_pages,
                                   window_v_pages),
                       (page_table, window_table), positions, state, last)
    elif n == 2:
        def fwd(p, tokens, k_pages, v_pages, page_table, positions, *state,
                last=None):
            return run(p, tokens, (k_pages, v_pages), (page_table,),
                       positions, state, last)
    else:
        def fwd(p, tokens, latent_pages, page_table, positions, *state,
                last=None):
            return run(p, tokens, (latent_pages,), (page_table,), positions,
                       state, last)

    first = 3 + n + len(tables)     # of the state pool's arrays
    return jax.jit(fwd, donate_argnums=tuple(range(2, 2 + n)) + tuple(
        range(first, first + len(held))))


def _program_bytes(exe) -> int:
    """What the device must hold to run a compiled program, by the
    compiler's own count; 0 where the backend gives none."""
    try:
        m = exe.memory_analysis()
        return int(m.argument_size_in_bytes + m.output_size_in_bytes
                   - m.alias_size_in_bytes + m.temp_size_in_bytes)
    except Exception:
        return 0


class GenerationEngine:
    """Continuous-batching engine for one replica of a model family
    (a row of ``ray_tpu.models.MODEL_FAMILIES``)."""

    def __init__(self, model: str = "gpt2", model_cfg: Any = None,
                 engine_cfg: Optional[EngineConfig] = None,
                 params: Any = None, seed: int = 0):
        import jax

        from ..models import MODEL_FAMILIES, family_of

        self.cfg = engine_cfg or EngineConfig()
        if model_cfg is None:
            model_cfg = MODEL_FAMILIES[model].tiny()
        self.model_cfg = model_cfg
        family = family_of(model_cfg)
        self._model = family.module(model_cfg)
        if params is None:
            params = family.init(model_cfg, jax.random.PRNGKey(seed))
        self._params = params
        # What this engine computes on, as JAX reports it (stats()).
        self._device = chips.describe_devices()
        # Everything a sequence keeps on the device, by layer kind, with
        # its allocators, tables and counters.  One layer's mixer weights
        # (stats()["state"], where the cache has a state pool) are a fact
        # of the model's config.
        mixer_params = getattr(model_cfg, "mixer_params", None)
        self.cache = SequenceCache(
            family.cache(model_cfg), num_pages=self.cfg.num_pages,
            page_size=self.cfg.page_size, max_batch=self.cfg.max_batch,
            max_context=self.cfg.max_context, max_seq=model_cfg.max_seq,
            dtype=model_cfg.dtype,
            mixer_weight_bytes=mixer_params()
            * np.dtype(model_cfg.param_dtype).itemsize
            if mixer_params else 0)

        self._fwd = jit_forward(self._model)
        # A config with a residual kind (models/decoder.py Residual):
        # what the kind says of itself and, from the LAST delivered
        # program, what its maps sowed (stats()["residual"]); else None.
        kind = getattr(model_cfg, "residual", None)
        self._residual: Optional[Dict[str, Any]] = kind and {
            **kind.describe(model_cfg),
            "max_row_sum_err": None, "max_col_sum_err": None}
        self._sampler, self._last_rows = jit_sampler(self.cfg.max_batch)
        # Each row's latest token id, [max_batch, 1] int32, on the
        # device from the first feed on: what a decode step takes as its
        # tokens.  Every program's ids are fed into it at launch.
        self._feeder = jit_feed()
        self._all_rows = np.arange(self.cfg.max_batch, dtype=np.int32)
        self._tokens: Any = np.zeros_like(self._all_rows)[:, None]
        # Launched programs whose ids are unread, oldest first: one
        # between steps, two while the older one is being delivered.
        self._flights: "deque[_Flight]" = deque()
        self._pipeline = {"launched_ahead": 0, "rows_discarded": 0}
        self._drains = dict.fromkeys(("evict", "error", "empty", "stop"),
                                     0)
        # The ledger of delivered programs by name (stats()["runs"]),
        # the seconds between a delivery and a later launch, those of
        # intervals that held a compile, and the last delivery's instant
        # on the phases' wall clock (``_file``).
        self._runs: Dict[str, Dict[str, Any]] = {}
        self._runs_unpaced_s = self._runs_voided_s = 0.0
        self._delivered_at: Optional[float] = None
        self._compiled = False      # ``_call`` compiled since a launch
        # AOT executables by program name (lower().compile()): the
        # compile is timed and the program registered with the xprof
        # plane (rt perf).
        self._exe_cache: Dict[str, Any] = {}
        self._compile_seconds: Dict[str, float] = {}
        # The largest program compiled here, by the compiler's own total
        # (arguments + outputs - aliased + temporaries): taken once per
        # compile, so stats() asks the device nothing.
        self._peak_program_bytes = 0

        self._lock = threading.Lock()
        self._wake = threading.Condition(self._lock)
        self._waiting: "deque[_Sequence]" = deque()
        self._running: List[_Sequence] = []
        self._cancelled: set = set()
        self._seqs: Dict[int, _Sequence] = {}
        self._ids = itertools.count(1)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._last_error: Optional[str] = None
        self._step_errors = 0
        self._steps = 0
        self._last_batch = 0
        self._tokens_total = 0
        self._prefill_tokens_total = 0
        # what the prefill programs computed: each prompt's bucket
        self._prefill_bucket_tokens = 0
        self._evictions = 0
        self._prefills = 0
        self._compiles = 0
        # Routing counters of a model with experts, cumulative over the
        # DECODE runs (stats()["moe"]) and, apart, over the prefills
        # (stats()["moe_prefill"]); both stay empty for a dense model.
        self._moe: Dict[str, Dict[str, int]] = {"decode": {}, "prefill": {}}
        # What the sampler was handed, counted from the packed rows:
        # ``steps`` launches (one a decode step, one a prefill), of which
        # ``steps_sampled`` held a row with temperature > 0 and took the
        # search; the others were argmax alone (stats()["sampling"]).
        self._sampling = {"rows_greedy": 0, "rows_sampled": 0,
                          "steps": 0, "steps_sampled": 0}
        # A step's leaves are handed over with the step's own time in
        # one go, under the lock: a stats() taken mid-step still sums up.
        self._phases = Phases(PHASE_LEAVES, "llm.other", lock=self._lock,
                              cpu_every=16)
        self._phase = self._phases.leaf
        # inside _prefill, leaves or not: wall and CPU seconds
        self._prefill_wall_s = self._prefill_cpu_s = 0.0
        self._seq_seed = seed
        # TTFT phase accounting (engine-side): waiting-queue + prefill
        # totals and TPOT (inter-token gap) sums, read through
        # stats().
        self._waiting_s_total = 0.0
        self._prefill_s_total = 0.0
        self._ttft_requests = 0
        self._tpot_s_total = 0.0
        self._tpot_count = 0
        # Metric handles cached once: the registry dedupes by name, but
        # re-constructing a Metric per emitted token would pay name
        # validation + the global registry lock ~1k times/s.
        self._metrics = {}
        try:
            from ..util.metrics import (Counter, Gauge, Histogram,
                                        ttft_phase_histogram)

            self._metrics = {
                "tokens": Counter("rt_llm_tokens_total",
                                  "Tokens generated."),
                "prefill": Counter(
                    "rt_llm_prefill_tokens_total",
                    "Prompt tokens prefilled into the KV cache."),
                "evictions": Counter(
                    "rt_llm_evictions_total",
                    "Sequences evicted for KV-memory pressure "
                    "(recompute preemption)."),
                "batch": Gauge(
                    "rt_llm_batch_size",
                    "Sequences in the decode batch this engine step."),
                "waiting": Gauge("rt_llm_waiting",
                                 "Sequences queued for admission."),
                "tpot": Histogram(
                    "rt_llm_tpot_seconds",
                    "Inter-token (time-per-output-token) gap."),
                "ttft_phase": ttft_phase_histogram(),
            }
        except Exception:
            pass

    # ----------------------------------------------------------- API
    def start(self) -> "GenerationEngine":
        if self._thread is None or not self._thread.is_alive():
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name="llm-engine")
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        with self._wake:
            self._wake.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=10)
        if self._thread is None or not self._thread.is_alive():
            # the loop has ended: deliver the ids it left unread
            try:
                with self._phases.whole():
                    self._drain("stop")
            except Exception as e:  # noqa: BLE001
                self._poison(e)

    def submit(self, prompt: List[int],
               max_tokens: Optional[int] = None,
               params: Optional[SamplingParams] = None,
               seed: Optional[int] = None,
               request_id: Optional[str] = None,
               _warmup: bool = False) -> _Sequence:
        """Queue one generation request; returns its sequence handle
        (stream its frames with ``frames()``).  ``request_id`` opts
        the sequence into request tracing: waiting/prefill/decode
        spans tagged with the id, plus TTFT-phase histograms."""
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        if any(t < 0 or t >= self.model_cfg.vocab_size for t in prompt):
            raise ValueError("prompt token out of vocab range")
        if len(prompt) + 1 > self.cache.max_context:
            raise ValueError(
                f"prompt of {len(prompt)} tokens exceeds the engine's "
                f"max context {self.cache.max_context}")
        if params is not None:
            params.validate()
        sid = next(self._ids)
        seq = _Sequence(sid, prompt,
                        max_tokens or self.cfg.max_tokens_default,
                        params or SamplingParams(),
                        self._seq_seed + sid if seed is None else seed,
                        request_id=request_id, warmup=_warmup)
        with self._wake:
            self._seqs[sid] = seq
            self._waiting.append(seq)
            self._wake.notify_all()
        return seq

    def cancel(self, sid: int) -> None:
        """Evict a sequence (client disconnect): frees its KV pages and
        removes it from the running batch on the next step."""
        with self._wake:
            if sid in self._seqs and not self._seqs[sid].finished:
                self._cancelled.add(sid)
                self._wake.notify_all()

    def frames(self, seq: _Sequence,
               timeout_s: Optional[float] = None):
        """Yield a sequence's output frames until its terminal frame
        ({"done": ...} or {"error": ...}); ``timeout_s`` bounds the gap
        between frames (default: the engine config's
        stream_idle_timeout_s)."""
        if timeout_s is None:
            timeout_s = self.cfg.stream_idle_timeout_s
        while True:
            deadline = time.time() + timeout_s
            while True:
                try:
                    fr = seq.out.get(timeout=1.0)
                    break
                except queue.Empty:
                    if self._thread is not None \
                            and not self._thread.is_alive() \
                            and not self._stop.is_set():
                        raise RuntimeError(
                            "generation engine thread died"
                            + (f": {self._last_error}"
                               if self._last_error else ""))
                    if time.time() > deadline:
                        raise TimeoutError(
                            f"no frame from sequence {seq.sid} in "
                            f"{timeout_s}s")
            yield fr
            if "done" in fr or "error" in fr:
                return

    def generate(self, prompt: List[int],
                 max_tokens: Optional[int] = None,
                 params: Optional[SamplingParams] = None,
                 seed: Optional[int] = None,
                 request_id: Optional[str] = None) -> List[int]:
        """Blocking convenience: submit and collect all tokens."""
        seq = self.submit(prompt, max_tokens, params, seed,
                          request_id=request_id)
        out: List[int] = []
        for fr in self.frames(seq):
            if "token" in fr:
                out.append(fr["token"])
            if "error" in fr:
                raise RuntimeError(fr["error"])
        return out

    def warmup(self) -> None:
        """Pay prefill+decode compilation before real traffic (the
        serve deployment calls this at replica init so the first
        request's TTFT isn't compile-bound)."""
        running = self._thread is not None and self._thread.is_alive()
        if not running:
            self.start()
        seq = self.submit([0, 1], max_tokens=2, _warmup=True)
        for fr in self.frames(seq):
            if "error" in fr:
                raise RuntimeError(fr["error"])
        if not running:
            self.stop()

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                # Largest compiled forward, by memory_analysis(): what
                # the device must hold to run it (the allocator's peak
                # leaves program temporaries out).
                "peak_hbm_bytes": self._peak_program_bytes,
                # Where step() spent its time, cumulative seconds: the
                # leaves and llm.other sum to step_s (phase_cpu_s,
                # step_cpu_s: the engine thread's CPU time in the steps
                # of cpu_sample).
                **self._phases.totals(),
                "prefills": self._prefills,
                "compiles": self._compiles,
                # What the sequences hold of the device's caches and what
                # the decode steps read of it: ``kv_pages_used`` /
                # ``_total`` (``kv_pages`` by group, where there are two),
                # ``attention``, and ``state`` where there is a state pool.
                **self.cache.stats(),
                "running": len(self._running),
                "waiting": len(self._waiting),
                "steps": self._steps,
                "tokens_generated": self._tokens_total,
                "prefill_tokens": self._prefill_tokens_total,
                # ... and what their buckets made of them: the padding
                # a power-of-two bucket costs is the difference
                "prefill_bucket_tokens": self._prefill_bucket_tokens,
                "evictions": self._evictions,
                "max_context": self.cache.max_context,
                "step_errors": self._step_errors,
                "last_error": self._last_error,
                # Compiled programs (forwards, the sampler, the
                # placement of a prefill's row) by name -> compile seconds.
                "programs": dict(self._compile_seconds),
                "sampling": dict(self._sampling),
                # The depth-one pipeline: programs launched while
                # another's ids were unread, drains by cause, launched
                # rows whose result was dropped (EOS, cancellation).
                "pipeline": {**self._pipeline,
                             "drains": dict(self._drains)},
                # Every delivered program by name: ``runs``, ``rows``
                # (delivered or discarded), ``tokens`` (positions
                # computed), and what each cost the loop, ids on the
                # host to ids on the host: ``paced_s``, and the same
                # intervals by power-of-two ms (``by_ms`` counts,
                # ``s_by_ms`` seconds).  Between two instants with
                # nothing in flight, paced + unpaced + voided = from
                # the last delivery before to the last one since.
                "runs": {name: _run_entry(name, **run)
                         for name, run in self._runs.items()},
                "runs_unpaced_s": self._runs_unpaced_s,
                "runs_voided_s": self._runs_voided_s,
                "device": dict(self._device),
                # TTFT phase + TPOT accounting.
                "ttft_requests": self._ttft_requests,
                "ttft_waiting_s_total": self._waiting_s_total,
                "ttft_prefill_s_total": self._prefill_s_total,
                "tpot_s_total": self._tpot_s_total,
                "tpot_count": self._tpot_count,
                # Routing of the decode runs (absent for a dense model):
                # layer_runs = runs x layers; pairs = real rows x k x
                # layers; experts_hit and max_load summed over layers
                # and runs.
                **({"moe": dict(self._moe["decode"])}
                   if self._moe["decode"] else {}),
                # Of the prefills, one run each: ``compact`` counts the
                # layer runs that took the experts' compact branch.
                **({"moe_prefill": dict(self._moe["prefill"])}
                   if self._moe["prefill"] else {}),
                # Of a residual path that is not one stream (absent
                # otherwise): streams, sublayers, the Sinkhorn's rounds,
                # and how far the last program's stream maps lay from
                # doubly stochastic over its live rows.
                **({"residual": dict(self._residual)}
                   if self._residual else {}),
            }

    # ------------------------------------------------------ engine loop
    def _loop(self) -> None:
        while not self._stop.is_set():
            with self._wake:
                while (not self._waiting and not self._running
                       and not self._cancelled
                       and not self._stop.is_set()):
                    with annotate("llm.idle"):
                        self._wake.wait(timeout=0.5)
                if self._stop.is_set():
                    break
            try:
                self.step()
            except Exception as e:  # noqa: BLE001
                self._poison(e)

    def _poison(self, e: Exception) -> None:
        """Error-retire the in-flight sequences (their device/pool
        state may be mid-mutation), those of a launched program whose
        ids are unread too (a device error surfaces at the fetch), but
        KEEP the engine loop alive: the replica stays routable and
        health-checked either way, so dying here would brick it for
        every future request over one transient step failure."""
        self._last_error = repr(e)
        self._step_errors += 1
        with self._wake:
            seqs = list(self._running) + list(self._waiting) + [
                seq for flight in self._flights for seq, _ in flight.rows]
            self._drains["error"] += bool(self._flights)
            self._running.clear()
            self._waiting.clear()
            self._flights.clear()
        self._tokens = np.zeros_like(self._all_rows)[:, None]
        for s in seqs:
            self._retire(s, error=repr(e))

    def step(self) -> Dict[str, Any]:
        """ONE engine iteration: cancellations -> admissions (each a
        prefill LAUNCHED) -> one batched decode step LAUNCHED; after
        each launch the ids of the program launched before it are
        fetched and delivered (tokens, frames, retirement), while the
        device runs the new one.  It returns with one program in
        flight, its ids unread, unless nothing is left to launch: then
        those ids are delivered here too, so the last step of a
        sequence ends with its frames out.  The pipeline also drains
        before an eviction; a device error surfaces at a fetch.  Public
        for deterministic single-step tests."""
        with self._phases.whole():
            with annotate("llm.step", step=self._steps,
                          running=len(self._running),
                          waiting=len(self._waiting)):
                with self._phase("llm.cancel"):
                    self._process_cancellations()
                with annotate("llm.admit"):
                    (w0, c0), pw, pc = (self._phases.clocks(),
                                        self._prefill_wall_s,
                                        self._prefill_cpu_s)
                    try:
                        self._admit()
                    finally:        # self time: the prefills apart
                        w1, c1 = self._phases.clocks()
                        self._phases.add(
                            "llm.admit",
                            w1 - w0 - (self._prefill_wall_s - pw),
                            c1 - c0 - (self._prefill_cpu_s - pc))
                if self._running:
                    with annotate("llm.decode", batch=len(self._running)):
                        self._decode_step()
                if not self._running:
                    self._drain("empty")
                self._last_batch = len(self._running)
                with self._phase("llm.publish"):
                    self._publish_gauges()
            self._steps += 1
        return {"running": len(self._running),
                "waiting": len(self._waiting)}

    def _process_cancellations(self) -> None:
        with self._lock:
            cancelled, self._cancelled = self._cancelled, set()
        for sid in cancelled:
            seq = self._seqs.get(sid)
            if seq is None or seq.finished:
                continue
            seq.cancelled = True
            with self._lock:
                if seq in self._running:
                    self._running.remove(seq)
                if seq in self._waiting:
                    self._waiting.remove(seq)
            # a program in flight may hold a row of it: dropped at its
            # delivery
            self._retire(seq, reason="cancelled")

    def _admit(self) -> None:
        """Step-granularity admission: pull waiting sequences into the
        running batch (each admission = one prefill forward), bounded
        by max_batch (a free row), the page pool, and the per-step token
        budget."""
        budget = self.cfg.prefill_token_budget - len(self._running)
        while True:
            with self._lock:
                if not self._waiting or \
                        len(self._running) >= self.cfg.max_batch:
                    return
                seq = self._waiting[0]
                cost = _bucket(len(seq.tokens))
                # Always make progress when nothing is running yet.
                if cost > budget and self._running:
                    return
                oversized = not self.cache.fits(len(seq.tokens))
                if not oversized \
                        and not self.cache.take(seq.held, len(seq.tokens)):
                    return      # wait for frees/retirements
                self._waiting.popleft()
            if oversized:
                self._retire(seq, error="sequence exceeds KV pool capacity")
                continue
            budget -= cost
            try:
                self._prefill(seq)
            except Exception as e:  # noqa: BLE001
                # The seq is out of _waiting and may be in neither
                # _running nor a flight yet — the loop's poison pass
                # can't see it then, so retire it here (frees its
                # pages, delivers the error frame) before re-raising
                # for the step-error accounting.
                self._retire(seq, error=repr(e))
                raise

    def _call_fwd(self, kind: str, tokens, tables, positions, slots,
                  **served):
        """The forward of this token shape (``llm_decode``, or
        ``llm_prefill[bucket]``) over the caches, which it updates
        (``tables``: the cache's, of this launch):
        returns (the program's name, logits, (the routing counters or None,
        the residual kind's or None)).  ``slots`` is
        each row's slot of the state pool (a row without a sequence:
        the index outside the pool), taken by a model that has one.
        ``served`` is a prefill's ``last`` (``jit_forward``): the logits
        are then that one position's."""
        name = f"llm_{kind}[{tokens.shape[1]}]" \
            if kind == "prefill" else f"llm_{kind}"
        logits, *rest = self._call(
            self._fwd, name, self._params, tokens,
            *self.cache.args(tables, positions, slots), **served)
        rest = self.cache.take_back(rest)
        residual = rest.pop() if self._residual else None
        return name, logits, (rest[0] if rest else None, residual)

    def _call(self, fn, name: str, *args, **kwargs):
        """Dispatch a jitted function through the AOT executable of this
        name (one name per shape: the engine's shapes are fixed).

        First sight of a name pays the one compile jit would pay anyway,
        but via ``lower().compile()`` so the compile is timed
        (``stats()["programs"]``), counted (``rt_xla_compiles_total``)
        and the program's cost/memory facts registered with the xprof
        plane.  There is one way to compile and run: a compile error or
        a run error surfaces as itself (the KV pages are donated, so
        there is nothing to retry with)."""
        cached = self._exe_cache.get(name)
        # A cache entry is only valid for the function it was compiled
        # from — if _fwd was swapped (fault injection, hot reload) the
        # stale executable must not keep serving.
        if cached is None or cached[0] is not fn:
            t0 = time.perf_counter()
            with annotate("llm.compile", program=name):
                exe = fn.lower(*args, **kwargs).compile()
            dt = time.perf_counter() - t0
            self._compile_seconds[name] = dt
            self._compiles += 1
            self._compiled = True
            self._peak_program_bytes = max(self._peak_program_bytes,
                                           _program_bytes(exe))
            try:
                from ..util import xprof

                xprof.register_compiled(name, exe, compile_seconds=dt)
            except Exception:
                pass    # registering with xprof is best-effort
            cached = self._exe_cache[name] = (fn, exe)
        return cached[1](*args, **kwargs)

    def _pack_sampling(self, rows: List[tuple]):
        """The sampler's per-row arguments for ``rows``, (sequence, its
        row) pairs: each draws its token number ``launched``; counted
        into stats()["sampling"]."""
        entries = [_IDLE_ROW] * self.cfg.max_batch
        for seq, row in rows:
            entries[row] = (seq.params, seq.seed, seq.launched)
        knobs, words = pack_rows(entries, self.cfg.max_batch)
        sampled = int(np.count_nonzero(knobs[:, 0]))
        counts = self._sampling
        counts["rows_sampled"] += sampled
        counts["rows_greedy"] += len(rows) - sampled
        counts["steps"] += 1
        counts["steps_sampled"] += sampled > 0
        return knobs, words

    # ------------------------------------------------- the pipeline
    def _feed(self, ids, rows) -> None:
        """``ids[j]`` becomes row ``rows[j]``'s latest token, on the
        device (a row index outside the batch: dropped)."""
        self._tokens = self._call(self._feeder, "llm_feed", self._tokens,
                                  ids, rows)

    def _is_last(self, seq: _Sequence, count: int) -> bool:
        """Whether ``seq``'s token number ``count`` (from 1) ends it by
        length: at ``max_tokens``, or where the NEXT write position,
        ``prompt_len + count - 1``, leaves the page-table window or the
        model's max_seq.  Asked of the launched count at launch and of
        the delivered count at delivery."""
        return count >= seq.max_tokens \
            or seq.prompt_len + count - 1 >= self.cache.max_context

    def _launched(self, seq: _Sequence) -> None:
        """Count the program just launched for ``seq``.  At its length
        limit it is not launched again and gives its row and pages back
        now, one program before its last frame: the device runs programs
        in launch order, so whatever takes them next writes after it."""
        seq.launched += 1
        if self._is_last(seq, seq.launched):
            with self._lock:
                if seq in self._running:
                    self._running.remove(seq)
            self.cache.release(seq.held)

    def _launch(self, flight: _Flight) -> None:
        """``flight`` is on the device's queue: now read the ids of the
        program before it and do their bookkeeping, under the leaves of
        the annotation that launched ``flight``."""
        self._pipeline["launched_ahead"] += bool(self._flights)
        self._flights.append(flight)
        if self._compiled:
            # Its launch compiled, while the programs before it were in
            # the air: their intervals are no program's pace.
            self._compiled = False
            for held in self._flights:
                held.void = True
        if len(self._flights) > 1:
            self._deliver(f"llm.{flight.kind}")

    def _drain(self, cause: str) -> None:
        """Read every launched program's ids before going on."""
        if self._flights:
            self._drains[cause] += 1
        while self._flights:
            self._deliver(f"llm.{self._flights[0].kind}")

    def _deliver(self, leaves: str) -> None:
        """Fetch the oldest launched program's ids and deliver them: a
        token, a frame and perhaps retirement for each of its rows; a
        row whose sequence ended meanwhile (EOS, cancellation) is
        dropped.  A device error surfaces at the fetch, with the flight
        still listed for the poison pass."""
        flight = self._flights[0]
        run = self._runs.get(flight.name)
        if run is None:
            with self._lock:
                run = self._runs[flight.name] = {
                    "runs": 0, "rows": 0,
                    "by_ms": [0] * RUN_BINS, "s_by_ms": [0.0] * RUN_BINS}
        # the annotation names the program whose ids it reads, not the
        # one just launched: a capture's k-th forward run to end and its
        # k-th ``.fetch`` to end are the same program
        with self._phase(leaves + ".fetch", program=flight.name,
                         run=run["runs"]) as fetch:
            ids = np.asarray(flight.ids).tolist()   # [max_batch] int32
            per_layer = None if flight.moe is None \
                else np.asarray(flight.moe)         # [layers, 4]
            if flight.residual is not None:
                row, col = np.asarray(flight.residual).tolist()
                with self._lock:
                    self._residual.update(max_row_sum_err=row,
                                          max_col_sum_err=col)
        self._flights.popleft()
        self._file(flight, run, fetch.ended)
        with self._phase(leaves + ".sample"):
            if per_layer is not None:
                from ..ops.moe import MOE_COUNTERS

                adds = dict(zip(MOE_COUNTERS, per_layer.sum(axis=0)),
                            layer_runs=len(per_layer))
                kept = self._moe[flight.kind]
                with self._lock:
                    for key in _MOE_KEPT[flight.kind]:
                        kept[key] = kept.get(key, 0) + int(adds[key])
            for seq, row in flight.rows:
                if seq.finished:
                    self._pipeline["rows_discarded"] += 1
                    continue
                self._emit_token(seq, ids[row])
                if flight.admitted is not None:
                    t_first = time.time()
                    self._prefill_s_total += t_first - flight.admitted
                    self._observe_phase("prefill",
                                        t_first - flight.admitted)
                    self._req_span(seq, "prefill", flight.admitted,
                                   t_first,
                                   tags={"prompt_tokens": seq.prompt_len})
                    seq.first_token_ts = t_first

    def _file(self, flight: _Flight, run: Dict[str, Any],
              delivered: float) -> None:
        """Enter a delivered program in the ledger: its interval runs
        from the later of the delivery before it and its own launch
        (both the leaves' own clock readings) to ``delivered``, the end
        of its ``.fetch`` leaf.  Across a drain, the poison pass or an
        idle wait the launch is the later one, so no interval spans
        them: that time is ``runs_unpaced_s``."""
        last, self._delivered_at = self._delivered_at, delivered
        began = flight.launched_at if last is None \
            else max(last, flight.launched_at)
        took = delivered - began
        with self._lock:
            run["runs"] += 1
            run["rows"] += len(flight.rows)
            if last is not None:
                self._runs_unpaced_s += began - last
            if flight.void:
                self._runs_voided_s += took
            else:
                i = min(int(took * 1e3).bit_length(), RUN_BINS - 1)
                run["by_ms"][i] += 1
                run["s_by_ms"][i] += took

    def _prefill(self, seq: _Sequence) -> None:
        w0, c0 = self._phases.clocks()
        try:
            with annotate("llm.prefill", seq=seq.sid,
                          request_id=seq.request_id or "",
                          prompt_tokens=len(seq.tokens),
                          bucket=_bucket(len(seq.tokens))):
                self._prefill_annotated(seq)
        finally:
            self._prefills += 1
            w1, c1 = self._phases.clocks()
            self._prefill_wall_s += w1 - w0
            self._prefill_cpu_s += c1 - c0

    def _prefill_annotated(self, seq: _Sequence) -> None:
        n = len(seq.tokens)
        # First admission only (a recompute-preempted sequence
        # re-prefills but already emitted its first token — its
        # waiting/prefill phases were accounted the first time), and
        # never the warmup sequence (it pays the compiles).
        first_admission = seq.launched == 0 and not seq.warmup
        t_admit = time.time()
        if first_admission:
            waited = max(t_admit - seq.submitted_ts, 0.0)
            self._waiting_s_total += waited
            self._ttft_requests += 1
            self._observe_phase("engine_waiting", waited)
            self._req_span(seq, "engine_waiting", seq.submitted_ts,
                           t_admit)
        with self._phase("llm.prefill.pack"):
            pad = _bucket(n)
            tokens = np.zeros((1, pad), np.int32)
            tokens[0, :n] = seq.tokens
            # From position 0, always: a multi-row step attends among its
            # own rows and reads nothing of the pool (models/attention.py),
            # and a window layer's store keeps its last ``window`` rows on
            # that assumption.
            if seq.n_cached != 0:
                raise ValueError(
                    f"a prefill starts at position 0, not {seq.n_cached}: "
                    "a multi-row step reads nothing of the cache")
            positions = np.full((1, pad), -1, np.int32)
            positions[0, :n] = np.arange(n)
            flight_rows = [(seq, 0)]
            tables = self.cache.tables(flight_rows, 1)
            sampling = self._pack_sampling(flight_rows)
            # its id, row 0 of the sampler's, to its own row
            feed_to = np.full(self.cfg.max_batch, self.cfg.max_batch,
                              np.int32)
            feed_to[0] = seq.held.slot
        with self._phase("llm.prefill.run") as launch:
            name, logits, counters = self._call_fwd(
                "prefill", tokens, tables, positions,
                np.asarray([seq.held.slot], np.int32),
                last=np.asarray([n - 1], np.int32))
            ids = self._call(
                self._sampler, "llm_sample",
                self._call(self._last_rows, "llm_last", logits),
                *sampling)
            self._feed(ids, feed_to)
        seq.n_cached = n
        self._prefill_tokens_total += n
        self._prefill_bucket_tokens += pad
        self._count("prefill", n)
        with self._lock:
            self._running.append(seq)
        self._launched(seq)
        self._launch(_Flight("prefill", name, ids, counters, flight_rows,
                             launch.began,
                             t_admit if first_admission else None))

    def _decode_step(self) -> None:
        """One batched decode forward over every running sequence, each
        in its own row, launched on the device's own copy of the ids."""
        B = self.cfg.max_batch
        with self._phase("llm.decode.pages"):
            dry = not self._ensure_pages(evict=not self._flights)
        if dry:
            # A victim re-prefills from its tokens, and what the unread
            # ids end may free the page: deliver them first.
            self._drain("evict")
            with self._phase("llm.decode.pages"):
                self._ensure_pages(evict=True)
        batch = list(self._running)
        if not batch:
            return
        with self._phase("llm.decode.pack"):
            positions = np.full((B, 1), -1, np.int32)
            slots = np.full(B, B, np.int32)
            flight_rows = [(seq, seq.held.slot) for seq in batch]
            tables = self.cache.tables(flight_rows, B)
            for seq, row in flight_rows:
                slots[row] = row
                positions[row, 0] = seq.n_cached
            self.cache.count_decode(positions)
            sampling = self._pack_sampling(flight_rows)
        with self._phase("llm.decode.run") as launch:
            name, logits, counters = self._call_fwd(
                "decode", self._tokens, tables, positions, slots)
            ids = self._call(self._sampler, "llm_sample", logits,
                             *sampling)
            self._feed(ids, self._all_rows)
        for seq in batch:
            seq.n_cached += 1
            self._launched(seq)
        self._launch(_Flight("decode", name, ids, counters, flight_rows,
                             launch.began))

    def _ensure_pages(self, evict: bool) -> bool:
        """A KV slot for every running sequence's next position; False
        where the pool ran dry and ``evict`` does not allow a victim."""
        for seq in list(self._running):
            if seq in self._running \
                    and not self._ensure_page(seq, evict):
                return False
        return True

    def _ensure_page(self, seq: _Sequence, evict: bool) -> bool:
        """Guarantee a KV slot for position ``seq.n_cached``; on pool
        exhaustion evict the most recently admitted other sequence
        (recompute preemption) and retry, if ``evict`` allows."""
        while not self.cache.grow(seq.held, seq.n_cached):
            if not evict:
                return False
            victim = None
            with self._lock:
                for cand in reversed(self._running):
                    if cand is not seq:
                        victim = cand
                        break
            if victim is None:
                with self._lock:
                    if seq in self._running:
                        self._running.remove(seq)
                self._retire(seq, error="KV pool exhausted with no "
                                        "evictable sequence")
                return True
            self._evict(victim)
        return True

    def _evict(self, victim: _Sequence) -> None:
        """Recompute preemption: drop the victim's pages, keep its
        tokens, park it at the FRONT of the waiting queue — it
        re-prefills (prompt + generated) once pages free up, without
        re-emitting anything already streamed.  Nothing of it is in
        flight: the pipeline was drained."""
        with self._lock:
            if victim in self._running:
                self._running.remove(victim)
            self._waiting.appendleft(victim)
        self.cache.release(victim.held)
        victim.n_cached = 0
        self._evictions += 1
        self._count("evictions")

    def _emit_token(self, seq: _Sequence, tok: int) -> None:
        """The host's bookkeeping for one token the device chose."""
        seq.tokens.append(tok)
        seq.generated += 1
        self._tokens_total += 1
        self._count("tokens")
        now = time.time()
        if seq.generated > 1 and seq.last_token_ts is not None \
                and not seq.warmup:
            gap = max(now - seq.last_token_ts, 0.0)
            self._tpot_s_total += gap
            self._tpot_count += 1
            try:
                if self._metrics:
                    self._metrics["tpot"].observe(gap)
            except Exception:
                pass
        seq.last_token_ts = now
        seq.out.put({"token": tok, "index": seq.generated - 1})
        eos = self.cfg.eos_id is not None and tok == self.cfg.eos_id
        # By length it left the batch at its last launch; an EOS is
        # known only here, one program after that row's next launch.
        if eos or self._is_last(seq, seq.generated):
            with self._lock:
                if seq in self._running:
                    self._running.remove(seq)
            self._retire(seq, reason="eos" if eos else "length")

    def _retire(self, seq: _Sequence, reason: str = "",
                error: Optional[str] = None) -> None:
        if seq.finished:
            return
        seq.finished = True
        self.cache.release(seq.held)
        self._seqs.pop(seq.sid, None)
        if seq.first_token_ts is not None and \
                seq.last_token_ts is not None and seq.generated > 1:
            self._req_span(seq, "decode", seq.first_token_ts,
                           seq.last_token_ts,
                           tags={"tokens": seq.generated,
                                 "reason": error or reason})
        if error is not None:
            seq.out.put({"error": error})
        else:
            seq.out.put({"done": True, "reason": reason,
                         "n_tokens": seq.generated})

    def _req_span(self, seq: _Sequence, name: str, start: float,
                  end: float, tags: Optional[Dict[str, Any]] = None
                  ) -> None:
        """Record one lifecycle span for a request-traced sequence
        (no-op otherwise — untraced traffic pays nothing).  The span
        lands in the replica process's ring; the worker flush loop
        ships it to the controller sink for `rt trace`."""
        if not seq.request_id:
            return
        try:
            from ..util import spans

            spans.record_span(
                name, start, end, cat="llm",
                tags={"request_id": seq.request_id, "seq": seq.sid,
                      **(tags or {})})
        except Exception:
            pass

    def _observe_phase(self, phase: str, seconds: float) -> None:
        try:
            if self._metrics:
                self._metrics["ttft_phase"].observe(
                    seconds, tags={"phase": phase})
        except Exception:
            pass

    # -------------------------------------------------------- metrics
    def _publish_gauges(self) -> None:
        try:
            if self._metrics:
                self._metrics["batch"].set(float(self._last_batch))
                self._metrics["waiting"].set(
                    float(len(self._waiting)))
        except Exception:
            pass

    def _count(self, key: str, n: float = 1.0) -> None:
        try:
            if self._metrics:
                self._metrics[key].inc(n)
        except Exception:
            pass
