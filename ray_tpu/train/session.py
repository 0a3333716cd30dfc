"""Per-worker training session: report/get_checkpoint/rank context.

Role-equivalent to the reference's _TrainSession (ref:
train/_internal/session.py:112, report at :672, get_checkpoint :772,
get_dataset_shard :1098).  The session is process-global inside each
training worker; ``report`` ships metrics (+ an optional checkpoint
directory) to the trainer through the result-queue actor, with rank 0
owning checkpoint persistence.
"""

from __future__ import annotations

import os
import tempfile
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

from ..util.spans import Phases, annotate
from .checkpoint import Checkpoint
from .config import TelemetryConfig

_session: Optional["TrainSession"] = None

# The leaves of a step loop's period, on the loop's thread; the rest of
# a period is ``train.other``: the loop's own code, which in a loop that
# reads its loss is the wait for the device.  ``train.input.transfer``
# runs on the prefetch thread: its own sum and count, beside the period.
TRAIN_LEAVES = ("train.input.wait", "train.step.dispatch",
                "train.report.observe", "train.report.push")


def _new_phases() -> Phases:
    return Phases(TRAIN_LEAVES, "train.other")


@dataclass
class TrainSession:
    world_rank: int
    world_size: int
    local_rank: int
    local_world_size: int
    node_rank: int
    experiment_name: str
    result_queue: Any = None          # ActorHandle of _ResultQueue
    checkpoint: Optional[Checkpoint] = None
    dataset_shards: Dict[str, Any] = field(default_factory=dict)
    storage_dir: str = ""
    telemetry: Optional[TelemetryConfig] = None
    # Driver-issued per-attempt id, identical across the gang's ranks
    # and fresh on every (re)start — the sharded-save commit nonce
    # (save_id = "<step>:<attempt_id>"), so a re-save of a step whose
    # previous attempt was SIGKILLed mid-save can never commit that
    # attempt's stale shard indexes.
    attempt_id: str = ""
    _report_index: int = 0
    _last_report_ts: Optional[float] = None
    _clock: Any = time.monotonic  # injectable for telemetry tests
    # Drain plane: sticky interruption notice (a preemption/drain was
    # announced for a node hosting this gang).  Set by the throttled
    # result-queue poll; once set it never clears for this attempt.
    _interrupt: Optional[Dict[str, Any]] = None
    _last_interrupt_poll: float = 0.0
    _interrupt_poll_period_s: float = 1.0
    # Metric handles by name, each built at its first use and kept:
    # report() runs every step, and building one pays name validation
    # and the registry's global lock.
    _metrics: Dict[str, Any] = field(default_factory=dict)
    # Where the loop's time goes, period by period: a period runs from
    # the end of one report() to the end of the next.
    phases: Phases = field(default_factory=_new_phases)

    def report(self, metrics: Dict[str, Any],
               checkpoint: Optional[Checkpoint] = None) -> None:
        """In a profiler capture: ``train.report`` (tag ``step``: the
        period's number, which the period's ``train.step.dispatch``
        carries too) with ``train.report.observe`` (telemetry) and
        ``train.report.push`` (the blocking RPC to the result queue)
        inside.  Its end closes the period."""
        with annotate("train.report", step=self._report_index):
            self._report_index += 1
            with self.phases.leaf("train.report.observe"):
                self._observe_step(metrics)
            payload = {"rank": self.world_rank, "metrics": dict(metrics),
                       "index": self._report_index,
                       "checkpoint_path": checkpoint.path if checkpoint
                       else None}
            if checkpoint is not None and self.interrupted():
                # Tag the payload so the driver (and the metrics
                # history) can tell a checkpoint-on-notice from a
                # periodic save.
                payload["preempt_ckpt"] = True
            if self.result_queue is not None:
                import ray_tpu

                with self.phases.leaf("train.report.push"):
                    ray_tpu.get(self.result_queue.push.remote(payload))
        self.phases.close()

    def stats(self) -> Dict[str, Any]:
        return phase_stats(self.phases)

    def _metric(self, kind: str, name: str, description: str):
        m = self._metrics.get(name)
        if m is None:
            from ..util import metrics as metrics_mod

            m = self._metrics[name] = getattr(metrics_mod, kind)(
                name, description)
        return m

    # ------------------------------------------------- drain/preemption
    def interruption(self) -> Optional[Dict[str, Any]]:
        """The drain notice for this gang, or None.  When a node
        hosting the gang enters DRAINING (preemption notice or ``rt
        drain``), the trainer driver flags the run's result queue; the
        session polls that flag (throttled to one RPC per
        ``_interrupt_poll_period_s``) so a per-step check costs ~0.

        The returned dict carries ``reason``, ``node_id`` and
        ``deadline`` (unix time the node is expected to die) — the
        budget rank 0 has for a checkpoint-on-notice.  Polling
        continues after the first notice: the queue keeps the
        earliest-deadline notice, and a tighter one arriving later
        (a real preemption during a leisurely operator drain) must
        replace the stale budget."""
        if self.result_queue is None:
            return self._interrupt
        now = self._clock()
        if now - self._last_interrupt_poll < \
                self._interrupt_poll_period_s:
            return self._interrupt
        self._last_interrupt_poll = now
        try:
            import ray_tpu

            latest = ray_tpu.get(
                self.result_queue.interrupt_info.remote())
            if latest is not None:
                self._interrupt = latest
        except Exception:
            pass  # queue dying usually means the gang is too
        return self._interrupt

    def interrupted(self) -> bool:
        """True once a drain/preemption notice covers this gang — the
        train loop should checkpoint (rank 0) and keep going; the
        controller restarts from that checkpoint without burning a
        ``max_failures`` slot."""
        return self.interruption() is not None

    def _observe_step(self, metrics: Dict[str, Any]) -> None:
        """Per-step telemetry: the report cadence IS the step cadence,
        so the delta between reports is the end-to-end step time (incl.
        data wait + host overhead); tokens/sec and achieved MFU derive
        from the declared TelemetryConfig figures."""
        try:
            now = self._clock()
            last, self._last_report_ts = self._last_report_ts, now
            step = metrics.get("step", self._report_index)
            self._metric("Gauge", "rt_train_step",
                         "Latest reported training step."
                         ).set(float(step))
            if last is None:
                return
            dt = max(now - last, 1e-9)
            self._metric("Histogram", "rt_train_step_time_seconds",
                         "Wall-clock between session.report calls "
                         "(per-step time).").observe(dt)
            # Timeline span per step, tagged step/rank: the cluster
            # timeline's per-rank step rows and the `rt timeline
            # --summary` critical path (slowest rank per step) are
            # built from these.
            try:
                from ..util import spans

                wall_end = time.time()
                spans.record_span(
                    "step", wall_end - dt, wall_end, cat="train_step",
                    tags={"step": int(float(step)),
                          "rank": self.world_rank})
            except Exception:
                pass
            tel = self.telemetry or TelemetryConfig()
            tokens = float(metrics.get("tokens",
                                       tel.tokens_per_step or 0.0))
            if tokens <= 0:
                return
            tps = tokens / dt
            self._metric("Gauge", "rt_train_tokens_per_sec",
                         "Per-worker training throughput.").set(tps)
            if tel.model_flops_per_token > 0:
                # The roofline's measured point: achieved model
                # FLOP/s per worker (rt perf plots it against the
                # attainable ceiling at the program's intensity).
                self._metric(
                    "Gauge", "rt_train_achieved_flops_per_sec",
                    "Achieved model FLOP/s per worker from the "
                    "declared FLOPs-per-token figure.").set(
                    tps * tel.model_flops_per_token)
                # Last: a device with no row in the chip peak table
                # (the CPU) raises here and gets no MFU gauge.
                peak = tel.resolved_peak_flops() * max(
                    tel.devices_per_worker, 1)
                self._metric(
                    "Gauge", "rt_train_mfu",
                    "Achieved model FLOPs utilization (0-1) from "
                    "the declared FLOPs-per-token figure.").set(
                    tps * tel.model_flops_per_token / peak)
        except Exception:
            pass  # telemetry must never fail a training step

    def iter_device_batches(self, batches, *, depth: int = 2,
                            transfer=None, sharding=None,
                            global_batch_size=None):
        """Device-prefetching wrapper for this worker's step loop; see
        the module-level ``iter_device_batches``."""
        return iter_device_batches(batches, depth=depth,
                                   transfer=transfer,
                                   sharding=sharding,
                                   global_batch_size=global_batch_size)

    def get_checkpoint(self) -> Optional[Checkpoint]:
        return self.checkpoint

    # ------------------------------------------- sharded checkpointing
    def save_sharded_checkpoint(self, tree: Any, *, step: int,
                                specs: Any = None,
                                mesh_axes: Optional[Dict[str, int]]
                                = None,
                                meta: Optional[Dict] = None,
                                metrics: Optional[Dict] = None,
                                report: bool = True,
                                wait_timeout_s: float = 120.0
                                ) -> Dict[str, Any]:
        """Collective sharded save into the run directory: EVERY rank
        calls this with the same ``step``; each writes only its local
        shards (jax arrays contribute their device shards, host trees
        the slices of this rank's mesh coordinates per
        ``specs``/``mesh_axes``), rank 0 writes the manifest last,
        commits atomically, and — with ``report`` — ships the
        committed checkpoint through ``session.report`` so the
        driver's CheckpointManager adopts it in place (no copy).
        Restore side: ``load_sharded_checkpoint`` reshards onto
        whatever world/mesh the elastic restart landed on."""
        if not self.storage_dir:
            raise RuntimeError(
                "sharded checkpointing needs the run storage dir; "
                "this session was initialized without one")
        from .sharded_checkpoint import save_sharded

        path = os.path.join(self.storage_dir,
                            f"checkpoint_{int(step):06d}")
        m = dict(meta or {})
        m.setdefault("step", int(step))
        m.setdefault("world_size", self.world_size)
        result = save_sharded(
            path, tree, specs=specs, mesh_axes=mesh_axes,
            process_index=self.world_rank,
            process_count=self.world_size, meta=m,
            wait_timeout_s=wait_timeout_s,
            # Per-attempt commit nonce: every rank of this attempt
            # derives the same value, and a restarted attempt gets a
            # fresh one — rank 0 refuses a dead attempt's indexes.
            save_id=(f"{int(step)}:{self.attempt_id}"
                     if self.attempt_id else None))
        if result["committed"] and report:
            self.report({"step": int(step), **(metrics or {})},
                        checkpoint=Checkpoint(path))
        return result

    def load_sharded_checkpoint(self, *, mesh=None, specs: Any = None,
                                target: Any = None,
                                validate: bool = True
                                ) -> Optional[Any]:
        """Restore the attempt's resume checkpoint (if it is in the
        sharded format), resharded onto ``mesh`` — the world-M half of
        an elastic N→M restart.  Returns None when there is no
        checkpoint; raises if the checkpoint exists but is a blob
        (use ``get_checkpoint().load_pytree`` for those)."""
        ckpt = self.get_checkpoint()
        if ckpt is None:
            return None
        if not ckpt.is_sharded:
            raise ValueError(
                f"{ckpt.path} is not a sharded checkpoint; load it "
                f"with Checkpoint.load_pytree/load_json")
        return ckpt.load_sharded(mesh=mesh, specs=specs,
                                 target=target, validate=validate)

    def get_dataset_shard(self, name: str = "train"):
        shard = self.dataset_shards.get(name)
        if shard is None:
            raise KeyError(f"no dataset shard {name!r} was provided to "
                           f"the trainer")
        return shard


def init_session(**kwargs) -> TrainSession:
    global _session
    _session = TrainSession(**kwargs)
    return _session


def get_session() -> TrainSession:
    if _session is None:
        raise RuntimeError("Not inside a training worker session")
    return _session


def shutdown_session() -> None:
    global _session
    _session = None


# -- public functional API (ray.train.report style) -----------------------

def report(metrics: Dict[str, Any],
           checkpoint: Optional[Checkpoint] = None) -> None:
    get_session().report(metrics, checkpoint)


# What a step loop outside a session accumulates in: there each call of
# the step closes a period (train_step.py).
_loose = _new_phases()


def step_account() -> Tuple[Phases, Optional[int]]:
    """Where this process's step loop accounts for its time, and the
    number of the period under way: the active session's accumulator and
    its report index; outside a session the process's own and None (the
    step counts its own calls)."""
    s = _session
    return (_loose, None) if s is None else (s.phases, s._report_index)


def phase_stats(phases: Phases) -> Dict[str, Any]:
    """Cumulative seconds over ``steps`` whole periods: the leaves
    (``phase_s``) and ``train.other`` sum to ``step_s``, on the loop
    thread's CPU clock ``phase_cpu_s`` and the rest to ``step_cpu_s``
    (read in every period here: ``cpu_sample`` repeats the wall's sums);
    ``longest_step_s`` is the worst single period (one stall on the
    machine shows here and not in a median); ``transfer_s`` over
    ``transfers`` is the prefetch thread's ``train.input.transfer``.  A
    period that compiled the step is in none of them."""
    with phases.lock:
        transfer_s, transfers = phases.apart_s.get(
            "train.input.transfer", (0.0, 0))
        return {"steps": phases.count, **phases.totals(),
                "transfer_s": transfer_s, "transfers": transfers,
                "longest_step_s": phases.longest_s}


def stats() -> Dict[str, Any]:
    """``phase_stats`` of this process's step loop (``step_account``)."""
    return phase_stats(step_account()[0])


def get_checkpoint() -> Optional[Checkpoint]:
    return get_session().get_checkpoint()


def get_dataset_shard(name: str = "train"):
    return get_session().get_dataset_shard(name)


def save_sharded_checkpoint(tree, *, step: int, specs=None,
                            mesh_axes=None, meta=None, metrics=None,
                            report: bool = True,
                            wait_timeout_s: float = 120.0):
    """Collective per-rank sharded save (see
    ``TrainSession.save_sharded_checkpoint``)."""
    return get_session().save_sharded_checkpoint(
        tree, step=step, specs=specs, mesh_axes=mesh_axes, meta=meta,
        metrics=metrics, report=report, wait_timeout_s=wait_timeout_s)


def load_sharded_checkpoint(*, mesh=None, specs=None, target=None,
                            validate: bool = True):
    """Reshard-on-restore of the attempt's resume checkpoint (see
    ``TrainSession.load_sharded_checkpoint``)."""
    return get_session().load_sharded_checkpoint(
        mesh=mesh, specs=specs, target=target, validate=validate)


def get_world_rank() -> int:
    return get_session().world_rank


def get_world_size() -> int:
    return get_session().world_size


def get_local_rank() -> int:
    return get_session().local_rank


def interrupted() -> bool:
    """True once a drain/preemption notice covers this gang."""
    return get_session().interrupted()


def interruption() -> Optional[Dict[str, Any]]:
    """The gang's drain notice ({reason, node_id, deadline}) or None."""
    return get_session().interruption()


@contextmanager
def checkpoint_dir():
    """Scratch dir for building a checkpoint before report()."""
    d = tempfile.mkdtemp(prefix="rt_ckpt_build_")
    yield d


@contextmanager
def checkpoint_on_notice():
    """Wrap the urgent save a train loop performs after
    ``interrupted()`` turns true: attributes the elapsed time to the
    ``checkpoint_on_notice`` goodput sub-phase (distinct from periodic
    ``checkpoint`` saves) and observes its duration histogram — the
    measured cost of converting an announced failure into a bounded
    one."""
    from ..util import goodput

    with goodput.timed_phase(
            "checkpoint_on_notice",
            "rt_train_ckpt_on_notice_seconds",
            "Rank-0 checkpoint save raced against a drain deadline."):
        yield


@contextmanager
def data_wait():
    """Wrap the blocking part of fetching the next batch: attributes
    the elapsed time to the ``data_stall`` goodput phase and observes
    the per-step data-wait histogram."""
    from ..util import goodput

    with step_account()[0].leaf("train.input.wait"), goodput.timed_phase(
            "data_stall", "rt_train_data_wait_seconds",
            "Time the step loop spent waiting on input data."):
        yield


def iter_device_batches(batches, *, depth: int = 2, transfer=None,
                        sharding=None, global_batch_size=None):
    """Overlap host->device transfer with compute: a feeder thread runs
    ``jax.device_put`` on batch N+1 (N+2, ... up to ``depth``) while
    the step loop computes on batch N, so the loop dequeues
    already-transferring device arrays instead of paying batch
    assembly + H2D latency inside the step (the device-side half of
    the zero-stall ingest chain; ref: tf.data-style prefetch-to-device
    / the reference's iter_torch_batches device prefetch).

    Any residual dequeue wait — the pipeline genuinely starving — is
    charged to the ``data_stall`` goodput phase and the
    ``rt_train_data_wait_seconds`` histogram, so the goodput summary
    shows exactly how far from zero-stall the input pipeline runs.

    ``sharding`` targets a ``NamedSharding``: each prefetched batch
    lands as a global array sharded along the mesh's data axis with NO
    host-side gather — in a multi-process world each rank contributes
    only the rows it loaded (pass ``global_batch_size`` when the
    global row count cannot be inferred, e.g. batch replicated over
    some processes).  ``transfer`` overrides placement entirely (e.g.
    ``lambda b: jax.device_put(b, sharding)``); the default is a plain
    ``jax.device_put`` onto the worker's default device.  Works with
    any iterable of pytrees (dict-of-ndarray batches included).
    Abandoning the iterator mid-stream stops and joins the feeder
    (shared lifecycle with the block prefetcher: util.prefetch).
    """
    from ..util.prefetch import iter_prefetched

    if transfer is None and sharding is not None:
        from .distributed import batch_transfer

        transfer = batch_transfer(sharding,
                                  global_batch_size=global_batch_size)
    if transfer is None:
        import jax

        def transfer(b):
            # device_put is async-dispatch: enqueue the transfer in the
            # feeder, let the consumer's compute overlap it.
            return jax.device_put(b)

    account = step_account()[0]

    def annotated_transfer(b):      # on the prefetch thread
        with account.apart("train.input.transfer"):
            return transfer(b)

    return iter_prefetched(batches, depth=depth,
                           transform=annotated_transfer,
                           wait_cm=data_wait,
                           thread_name="rt-device-prefetch")
