"""The node agent — per-node scheduler, worker pool, and object plane.

Role-equivalent to the reference's raylet (ref: src/ray/raylet/
node_manager.h:117 NodeManager, worker_pool.h:216 WorkerPool,
scheduling/cluster_task_manager.h + local_task_manager.h).  One agent per
host: grants worker leases against a resource ledger (hybrid
local-first/spillback policy), spawns and supervises worker processes,
owns the shared-memory store directory, serves node-to-node object
transfer, and holds placement-group bundle reservations (two-phase
prepare/commit, ref: gcs_placement_group_scheduler.h).

TPU note: the agent also owns the host's chip ledger — a lease that
demands ``TPU: k`` is granted k specific chip ids, which a worker that
has never run anything exports to libtpu before its first JAX backend
initialisation; every other worker is spawned kept off the chips, and a
worker is retired when its chip lease ends (core/chip_lease.py; the TPU
analogue of the reference's CUDA_VISIBLE_DEVICES isolation, ref:
python/ray/_private/accelerators/tpu.py).
"""

from __future__ import annotations

import argparse
import asyncio
import itertools
import json
import logging
import os
import signal
import subprocess
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Set, Tuple

from . import chip_lease
from .config import RuntimeConfig
from .ids import ActorID, NodeID, ObjectID, PlacementGroupID, WorkerID
from .object_store import SharedObjectStore, StoreDirectory
from .resources import ResourceSet, node_resources
from .rpc import (RemoteCallError, RpcClient, RpcError, RpcServer,
                  spawn_task)
from ..util import compile_cache

logger = logging.getLogger("ray_tpu.node_agent")


def pool_plan(*, target: int, idle: int, starting: int, leased: int,
              pending_spawns: int, burst: int, max_workers: int,
              active: int, draining: bool = False) -> int:
    """How many prestart workers to spawn THIS refill tick (pure —
    unit-tested without an agent).

    ``idle``/``starting``/``leased`` count non-actor workers of the env
    hash being refilled: a leased task worker returns to the pool, so
    it still satisfies the target, while an adopted actor worker never
    does.  ``pending_spawns`` vs ``burst`` is the spawn-storm
    hysteresis — at most ``burst`` forked-but-unregistered processes
    exist at once, so a refill after a mass adoption trickles the herd
    instead of forking it in one stampede.  A draining node never
    refills (its pool is being killed, not warmed)."""
    if draining or target <= 0:
        return 0
    deficit = target - idle - starting - leased
    if deficit <= 0:
        return 0
    budget = burst - pending_spawns
    room = max_workers - active
    return max(0, min(deficit, budget, room))


def warm_env_targets(now: float, default_target: int,
                     env_last_used: Dict[str, float],
                     ttl_s: float) -> Dict[str, int]:
    """Which runtime-env hashes the prestart pool keeps warm: the
    default env always, plus any hash adopted within ``ttl_s`` (each at
    the full target — the reference pops workers by runtime-env hash,
    worker_pool.h:216, so a hot non-default env deserves its own warm
    set)."""
    out = {"": default_target}
    for env_hash, ts in env_last_used.items():
        if env_hash and now - ts <= ttl_s:
            out[env_hash] = default_target
    return out


def _pid_alive(w: "WorkerEntry") -> bool:
    if w.proc is not None:
        return w.proc.poll() is None
    try:
        os.kill(w.pid, 0)
        return True
    except (ProcessLookupError, PermissionError):
        return False


@dataclass
class WorkerEntry:
    worker_id: WorkerID
    addr: str
    pid: int
    proc: Optional[subprocess.Popen] = None
    # starting | idle | leased | actor | retiring | dead
    state: str = "idle"
    actor_id: Optional[ActorID] = None
    lease_id: Optional[int] = None
    # Runtime-env identity: a worker only serves leases with a matching
    # env hash (ref: worker_pool.h:216 PopWorker runtime-env keying).
    env_hash: str = ""
    # Log plane: this worker's stdout/stderr file and the job its
    # current/last lease belongs to (log lines are attributed to it —
    # ref: _private/log_monitor.py job tagging).
    log_path: str = ""
    job_id: Optional[str] = None
    # True once this worker has served a lease and returned to the
    # idle pool: a waiter handed a recycled worker paid NO fork, so
    # the pool's cold-spawn (fork-latency) accounting must not count
    # it (doctor's exhaustion check keys off that counter).
    recycled: bool = False


@dataclass
class Lease:
    lease_id: int
    resources: ResourceSet
    worker: WorkerEntry
    chip_ids: List[int]
    pg_id: Optional[PlacementGroupID] = None
    bundle_index: int = -1
    blocked: bool = False
    # Connection tag of the OWNER process holding this lease (task/pool
    # leases only; actor leases are owned by the actor worker itself
    # and released on its exit).  Lets the agent reclaim leases whose
    # owner died without returning them — e.g. an actor killed while
    # caching a lease for reuse — instead of stranding the leased
    # worker and its resources forever.
    owner_tag: str = ""
    granted_ts: float = 0.0
    # Internal job hex of the submitting driver — resolves to the
    # multi-tenant submitted-job id through the controller's
    # heartbeat-distributed job view (quota enforcement + per-job
    # attribution in the lease ledger).
    job_id: str = ""


@dataclass
class _PendingLease:
    payload: Dict[str, Any]
    future: asyncio.Future
    enqueue_time: float = field(default_factory=time.time)


@dataclass
class _Bundle:
    pg_id: PlacementGroupID
    bundle_index: int
    resources: ResourceSet
    committed: bool = False
    in_use: ResourceSet = field(default_factory=ResourceSet)


class NodeAgent:
    def __init__(self, config: RuntimeConfig, session: str,
                 controller_addr: str, *,
                 num_cpus: Optional[float] = None,
                 num_tpus: Optional[float] = None,
                 custom_resources: Optional[Dict[str, float]] = None,
                 labels: Optional[Dict[str, str]] = None,
                 is_head: bool = False):
        self.config = config
        self.session = session
        self.controller_addr = controller_addr
        self.node_id = NodeID.from_random()
        self.is_head = is_head
        self.labels = labels or {}
        self.total = node_resources(
            num_cpus=num_cpus, num_tpus=num_tpus, extra=custom_resources,
            tpu_override_chips=config.tpu_chips_per_host)
        self.available = self.total.copy()
        n_chips = int(self.total.get("TPU"))
        self.free_chips: List[int] = list(range(n_chips))
        self.server = RpcServer()
        from .object_store import PoolObjectStore, create_store

        self.store = create_store(session, config)
        # Workers must use the SAME backend this agent resolved — a
        # silent per-process fallback would split the node across two
        # object planes.
        self._store_backend = ("pool" if isinstance(self.store,
                                                    PoolObjectStore)
                               else "segments")
        spill_dir = None
        if config.object_spill_enabled:
            spill_dir = os.path.join(
                config.session_dir_root, session, "spill",
                self.node_id.hex()[:8])
        self.directory = StoreDirectory(
            self.store, config.object_store_memory_bytes,
            spill_dir=spill_dir)
        self.workers: Dict[WorkerID, WorkerEntry] = {}
        self.leases: Dict[int, Lease] = {}
        self.bundles: Dict[Tuple[PlacementGroupID, int], _Bundle] = {}
        self.pending: List[_PendingLease] = []
        self._lease_counter = itertools.count(1)
        self._starting_workers = 0
        self._idle_q: List[WorkerEntry] = []
        self._worker_ready = asyncio.Event()
        self._pull_inflight: Dict[ObjectID, asyncio.Future] = {}
        # Fast releases that arrived before their registration (cross-
        # channel reorder); the late register must be dropped.
        self._early_released: set = set()
        # Coalesced location updates -> controller (ordered add/remove
        # pairs); flushed after a short window so a put/release burst
        # costs one bulk notify, not a call round trip per object.
        self._loc_buf: List = []
        self._loc_flush_scheduled = False
        self._loc_send_inflight = False
        self._ctl: Optional[RpcClient] = None
        self._peer_agents: Dict[str, RpcClient] = {}
        self._resource_view: Dict[Any, Dict] = {}
        # Drain lifecycle (preemption notice / `rt drain`): a draining
        # agent refuses new lease grants, redirects its queued lease
        # requests to live peers, and advertises the drain deadline in
        # its heartbeat so the controller/autoscaler can migrate work
        # and start a replacement BEFORE the node dies.
        self._draining = False
        self._drain_reason = ""
        self._drain_deadline = 0.0
        self._drain_replace = True
        # Lease-ledger view state (`rt list leases` / `rt doctor`):
        # owner-reported pipeline depth per lease, when an owner tag's
        # connection was first seen lost, and per-lease disconnect
        # anchors derived from it.
        self._owner_lease_depths: Dict[int, tuple] = {}
        self._owner_conn_lost_ts: Dict[str, float] = {}
        self._owner_disc_since: Dict[int, float] = {}
        # Multi-tenant quota view from heartbeat replies:
        # {internal_job_hex: {job, priority, quota, used}} — the
        # lease-grant path refuses (queues) grants that would run a
        # job over quota.  Last-reported local usage lets the grant
        # check overlay its own since-last-heartbeat deltas.
        self._job_view: Dict[str, Dict] = {}
        self._job_usage_reported: Dict[str, Dict[str, float]] = {}
        self._shutdown = asyncio.Event()
        self._spawned_procs: List[subprocess.Popen] = []
        # Warm-worker prestart pool (ref: worker_pool.h:216 PopWorker /
        # PrestartWorkers): idle workers pre-spawned per runtime-env
        # hash so actor/task creation ADOPTS a live process instead of
        # paying a full interpreter spawn.  Counters feed `rt
        # telemetry`, `rt doctor` (pool exhaustion), and the scale
        # benches' adoption-vs-cold-spawn report.
        self._pool_adoptions = 0
        self._pool_cold_spawns = 0
        self._cold_spawn_ts: List[float] = []  # ring for the 60s window
        self._spawned_total = 0
        self._env_specs: Dict[str, Dict] = {}      # hash -> runtime_env
        self._env_last_used: Dict[str, float] = {}
        self._refill_wakeup = asyncio.Event()
        # Worker startup-phase breakdown (spawn/import/connect stamped
        # into the worker hello; adopt measured grant-side).
        from ..util.metrics import Histogram

        self._startup_hist = Histogram(
            "rt_worker_startup_seconds",
            "Worker startup time by phase (spawn=fork->interpreter, "
            "import=module imports, connect=runtime connect+hello, "
            "adopt=lease-grant wait for a worker).",
            tag_keys=("phase",))
        # Batched actor-started relay: workers report their actor hello
        # here; the agent coalesces a creation fan-out into bulk
        # controller RPCs on a short window (one persistent connection,
        # a handful of frames — not one fresh dial per actor).
        self._actor_started_buf: List[Tuple[Dict, asyncio.Future]] = []
        self._actor_started_scheduled = False
        for name in [
            "request_lease", "return_lease", "lease_status",
            "cancel_lease_request", "list_leases", "report_lease_pool",
            "register_worker", "worker_heartbeat",
            "report_task_events", "report_metrics", "report_spans",
            "report_collective_entries",
            "jax_profile_workers",
            "task_blocked", "task_unblocked", "report_backlog",
            "register_object", "pull_object", "fetch_raw", "fetch_chunk",
            "delete_object", "owner_release_local", "make_room",
            "object_exists", "objects_exist", "store_stats",
            "prepare_bundle", "commit_bundle", "return_bundle",
            "restart_actor", "kill_worker", "report_actor_failure",
            "report_actor_started", "pool_stats",
            "preempt_pg_leases",
            "drain", "shutdown", "ping", "node_info", "list_workers",
            "list_worker_logs", "read_worker_log", "profile_worker",
            "stack_worker",
        ]:
            self.server.register(name, getattr(self, name))
        # Reclaim leases whose owner process died without returning
        # them (found via the new tracing tests: a killed actor that
        # had cached a task lease for reuse strands the leased worker
        # and its CPUs forever, starving every later task).
        self.server.on_connection_lost(self._on_owner_conn_lost)

    # -------------------------------------------------------------- startup
    async def start(self, port: int = 0) -> int:
        # Debug hook: `kill -USR2 <agent pid>` logs every live asyncio
        # task with its await stack (coroutine-level triage the
        # faulthandler thread dump can't see).
        def _dump_tasks(*_a):
            logger.error(
                "SCHEDSTATE pending=%d workers=%d idle_q=%d "
                "starting=%d spawns=%d available=%s total=%s "
                "leases=%s free_chips=%s by_env=%s acq=%s",
                len(self.pending), len(self.workers),
                len(self._idle_q), self._starting_workers,
                len(getattr(self, "_pending_spawns", {})),
                dict(self.available.amounts),
                dict(self.total.amounts),
                {lid: dict(l.resources.amounts)
                 for lid, l in self.leases.items()},
                self.free_chips,
                dict(getattr(self, "_starting_by_env", {})),
                dict(getattr(self, "_acquirers_by_env", {})))
            for t in asyncio.all_tasks():
                # Walk the cr_await chain so nested handler coroutines
                # show their INNERMOST suspension point, not just the
                # outer _dispatch frame.
                lines = []
                coro = t.get_coro()
                seen = 0
                while coro is not None and seen < 32:
                    seen += 1
                    frame = getattr(coro, "cr_frame", None) or \
                        getattr(coro, "gi_frame", None)
                    if frame is not None:
                        code = frame.f_code
                        lines.append(f"  {code.co_filename}:"
                                     f"{frame.f_lineno} "
                                     f"{code.co_name}")
                    nxt = getattr(coro, "cr_await", None) or \
                        getattr(coro, "gi_yieldfrom", None)
                    if nxt is coro:
                        break
                    coro = nxt
                logger.error("TASKDUMP %r\n%s", t,
                             "\n".join(lines) or "  <no frames>")

        try:
            asyncio.get_event_loop().add_signal_handler(
                signal.SIGUSR2, _dump_tasks)
        except (NotImplementedError, RuntimeError):
            pass
        # Preemption notice: GCP delivers SIGTERM seconds-to-minutes
        # before a spot VM dies.  Enter DRAINING instead of dying so
        # the grace window is spent migrating work (checkpoint-on-
        # notice, queued-lease redirect) rather than lost.  A REPEATED
        # SIGTERM forces immediate shutdown (operator escape hatch) —
        # but only once a SIGTERM already armed the deadline: the
        # first SIGTERM on a node mid `rt drain` is the real cloud
        # notice, and discarding its grace would kill gangs mid
        # checkpoint-on-notice.
        def _on_sigterm():
            if getattr(self, "_sigterm_drained", False):
                spawn_task(self.shutdown())
            elif self._draining:
                self._sigterm_drained = True
                now = time.time()
                grace = self.config.preemption_grace_s
                if self._drain_deadline > 0:
                    self._drain_deadline = min(self._drain_deadline,
                                               now + grace)
                else:
                    self._drain_deadline = now + grace
                asyncio.get_event_loop().call_later(
                    max(self._drain_deadline - now, 0.0),
                    lambda: spawn_task(self.shutdown()))
            elif not self.leases and not self.pending \
                    and not self.bundles:
                # Nothing to migrate: spending the grace window on an
                # idle node only slows down `rt stop` / graceful
                # teardown paths that relied on SIGTERM exiting.
                self._sigterm_drained = True
                spawn_task(self.shutdown())
            else:
                self._sigterm_drained = True
                spawn_task(self._begin_drain(
                    reason="preemption notice (SIGTERM)",
                    grace_s=self.config.preemption_grace_s,
                    shutdown_at_deadline=True))

        try:
            asyncio.get_event_loop().add_signal_handler(
                signal.SIGTERM, _on_sigterm)
        except (NotImplementedError, RuntimeError):
            pass
        await self.server.start(port)
        # Evictions from ANY shed site (read-window expiry, restore
        # pressure, register) must drop their controller locations, or
        # recovery probes poll dead copies until timeout.
        self._loop = asyncio.get_event_loop()

        def _on_evict(oids):
            # Through the ORDERED update queue (thread-safe hop onto
            # the loop): an immediate direct remove could overtake a
            # still-buffered add for the same oid and leave a ghost
            # location — every location mutation from this agent rides
            # one serialized, acked stream.
            def _q():
                for oid in oids:
                    self._queue_loc_update("remove", oid)

            self._loop.call_soon_threadsafe(_q)

        self.directory.on_evict = _on_evict
        self._ctl = RpcClient(self.controller_addr,
                              tag=f"agent-{self.node_id.hex()[:8]}",
                              connect_timeout=5.0)
        await self._ctl.connect()
        await self._ctl.call("register_node", {
            "node_id": self.node_id, "agent_addr": self.server.address,
            "resources": dict(self.total.amounts), "labels": self.labels,
            "is_head": self.is_head})
        # Event-loop lag ring: a starved agent loop (fork herds, big
        # frame decodes) shows up as rt_loop_lag_seconds in telemetry
        # and as an rt doctor event-loop-stall finding.
        from ..util.hotpath import LoopLagSampler

        self._loop_lag = LoopLagSampler(self._loop)
        self._loop_lag.start()
        spawn_task(self._heartbeat_loop())
        spawn_task(self._reap_loop())
        if self.config.log_to_driver:
            spawn_task(self._log_monitor_loop())
        if self.config.memory_monitor_refresh_ms > 0:
            spawn_task(self._memory_monitor_loop())
        for _ in range(self.config.worker_pool_min_workers):
            self._spawn_worker()
        spawn_task(self._prestart_refill_loop())
        return self.server.port

    async def _heartbeat_loop(self) -> None:
        period = self.config.raylet_heartbeat_period_ms / 1000.0
        first_miss = None
        last_metrics = 0.0
        self._last_busy = time.time()
        while not self._shutdown.is_set():
            try:
                now = time.time()
                if self.leases or self.bundles:
                    self._last_busy = now
                # Demand = queued lease requests + owner-reported
                # backlogs (lease requests are rate-limited per owner,
                # so queued tasks beyond the in-flight requests arrive
                # via report_backlog; ref: ReportWorkerBacklog in
                # normal_task_submitter.h).
                demands = self._demand_vector()
                # Snapshot ONCE and remember exactly what was sent:
                # recomputing after the RPC await would fold leases
                # granted mid-await into the "already reported" side
                # of the quota overlay and hide them from the check.
                job_usage = self._job_usage_local()
                if self.pending:
                    # Self-healing dispatch tick: a request requeued
                    # after a failed worker acquire has no event left
                    # to kick it; retry on the heartbeat cadence (ref:
                    # the raylet re-running ScheduleAndDispatchTasks
                    # periodically, node_manager.cc).
                    self._kick_scheduler()
                r = await self._ctl.call("heartbeat", {
                    "node_id": self.node_id,
                    "available": {k: max(v, 0.0) for k, v in
                                  self.available.amounts.items()},
                    "total": dict(self.total.amounts),
                    # Autoscaler inputs (ref: ray_syncer.proto:31-47
                    # idle_duration_ms + LoadMetrics demand vector).
                    "idle_s": now - self._last_busy,
                    "pending_demands": demands,
                    # Drain plane: the controller mirrors these into
                    # its node table (`rt drain` state, doctor's
                    # stale-drain check, autoscaler replacement).
                    # The deadline crosses hosts as REMAINING seconds
                    # — agent wall clocks can sit minutes off the
                    # controller's, and the stale-drain check compares
                    # against the controller clock (same receipt-clock
                    # discipline as flight-dump ages).
                    "draining": self._draining,
                    "drain_remaining_s": self._drain_remaining(),
                    "drain_reason": self._drain_reason,
                    "drain_replace": self._drain_replace,
                    # Multi-tenant accounting: plain-lease usage per
                    # internal job (PG-bound leases excluded — their
                    # bundles are counted controller-side).
                    "job_usage": job_usage,
                    # Prestart-pool occupancy for `rt status` / the
                    # dashboard node table.  Prestarted IDLE workers
                    # deliberately do NOT touch _last_busy above:
                    # a warm pool must never pin a node past its
                    # idle timeout (the autoscaler's if_idle reap
                    # and scale-down read idle_s).
                    "worker_pool": {
                        "idle": self._pool_counts("")[0],
                        "target": self._prestart_target(),
                        "adoptions": self._pool_adoptions,
                        "cold_spawns": self._pool_cold_spawns}})
                self._job_usage_reported = job_usage
                self._job_view = r.get("jobs") or {}
                now = time.time()
                if now - last_metrics >= \
                        self.config.metrics_report_period_s:
                    last_metrics = now
                    await self._ctl.call("report_metrics", {
                        "source": f"node-{self.node_id.hex()[:8]}",
                        "snapshot": self._node_metrics_snapshot()})
                if r.get("reregister"):
                    # Fresh (possibly restarted) controller: rebuild our
                    # node row AND our object locations (the location
                    # directory is not persisted; ref: NotifyGCSRestart
                    # node_manager.proto:387 resend path).
                    await self._ctl.call("register_node", {
                        "node_id": self.node_id,
                        "agent_addr": self.server.address,
                        "resources": dict(self.total.amounts),
                        "labels": self.labels, "is_head": self.is_head})
                    objs = [(oid, ent.size) for oid, ent in
                            [(o, self.directory.lookup(o))
                             for o in self.directory.all_ids()]
                            if ent is not None]
                    if objs:
                        await self._ctl.call("publish_locations", {
                            "node_id": self.node_id, "objects": objs})
                first_miss = None
            except RpcError:
                now = time.time()
                if first_miss is None:
                    first_miss = now
                # Tolerate a restart window: RpcClient re-dials on the
                # next call, so a controller that comes back on the same
                # address within the grace resumes us transparently.
                if now - first_miss > \
                        self.config.controller_reconnect_grace_s:
                    logger.warning("controller unreachable for %.0fs; "
                                   "shutting down",
                                   now - first_miss)
                    await self.shutdown()
                    return
            await asyncio.sleep(period)

    @staticmethod
    def _memory_usage_fraction() -> float:
        """Host memory pressure from /proc/meminfo (ref:
        common/memory_monitor.h GetMemoryBytes — cgroup-aware there;
        host-level here, which matches one-agent-per-TPU-host)."""
        total = avail = None
        try:
            with open("/proc/meminfo") as f:
                for line in f:
                    if line.startswith("MemTotal:"):
                        total = int(line.split()[1])
                    elif line.startswith("MemAvailable:"):
                        avail = int(line.split()[1])
                    if total is not None and avail is not None:
                        break
        except OSError:
            return 0.0
        if not total or avail is None:
            return 0.0  # no MemAvailable (old kernel): monitor inert
        return 1.0 - avail / total

    def _pick_oom_victim(self) -> Optional["Lease"]:
        """Retriable-task-first, newest-first (ref:
        worker_killing_policy.h RetriableFIFOWorkerKillingPolicy):
        normal tasks retry transparently; actors lose state, so they go
        last — and only when they are restartable is that survivable."""
        task_leases = [ls for ls in self.leases.values()
                       if ls.worker.state == "leased"]
        if task_leases:
            return max(task_leases, key=lambda ls: ls.lease_id)
        actor_leases = [ls for ls in self.leases.values()
                        if ls.worker.state == "actor"]
        if actor_leases:
            return max(actor_leases, key=lambda ls: ls.lease_id)
        return None

    async def _memory_monitor_loop(self) -> None:
        """Kill workers under host memory pressure instead of letting
        the OS OOM killer take the agent (ref: memory_monitor.h +
        worker_killing_policy.h)."""
        period = self.config.memory_monitor_refresh_ms / 1000.0
        threshold = self.config.memory_usage_threshold
        while not self._shutdown.is_set():
            await asyncio.sleep(period)
            usage = self._memory_usage_fraction()
            if usage <= threshold:
                continue
            victim = self._pick_oom_victim()
            if victim is None:
                continue
            w = victim.worker
            logger.warning(
                "memory pressure %.1f%% > %.1f%%: killing worker %s "
                "(lease %d) to reclaim memory", usage * 100,
                threshold * 100, w.pid, victim.lease_id)
            try:
                if w.proc is not None:
                    w.proc.kill()
                else:
                    os.kill(w.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
            # The reap loop notices the death, releases the lease, and
            # the owner's retry machinery resubmits retriable work.

    async def _reap_loop(self) -> None:
        """Detect worker process exits (ref: worker_pool.cc monitoring)."""
        while not self._shutdown.is_set():
            await asyncio.sleep(0.1)
            for w in list(self.workers.values()):
                if w.proc is not None and w.proc.poll() is not None \
                        and w.state != "dead":
                    await self._on_worker_exit(w)
            # Workers that died before registering.
            pending = getattr(self, "_pending_spawns", {})
            for pid, (proc, env_hash) in list(pending.items()):
                if proc.poll() is not None:
                    pending.pop(pid, None)
                    self._starting_done(env_hash)
                    self._worker_ready.set()
                    logger.warning("worker pid %s died before registering "
                                   "(code %s)", pid, proc.returncode)

    async def _on_worker_exit(self, w: WorkerEntry) -> None:
        prev_state = w.state
        w.state = "dead"
        self.workers.pop(w.worker_id, None)
        if w in self._idle_q:
            self._idle_q.remove(w)
        # A death frees a pool slot: waiters in _acquire_worker must
        # re-evaluate their spawn budget or they sleep out their full
        # timeout while the pool sits empty.
        self._worker_ready.set()
        self._kick_refill()
        if w.lease_id is not None and w.lease_id in self.leases:
            self._release_lease(self.leases[w.lease_id], worker_back=False)
        if prev_state == "actor" and w.actor_id is not None:
            code = w.proc.returncode if w.proc else None
            try:
                await self._ctl.call("actor_died", {
                    "actor_id": w.actor_id,
                    "reason": f"worker exited with code {code}"})
            except RpcError:
                pass
        await self._forward_flight_dump(w)
        logger.info("worker %s exited (state=%s)", w.pid, prev_state)

    async def _forward_flight_dump(self, w: WorkerEntry) -> None:
        """If the dead worker left a flight-recorder dump, ship it to
        the controller so postmortems work cluster-wide (the file stays
        on disk for offline triage)."""
        path = os.path.join(
            self.config.session_dir_root, self.session, "flight",
            f"worker-{self.node_id.hex()[:8]}-{w.pid}.json")
        try:
            if not os.path.exists(path):
                return
            with open(path) as f:
                data = json.load(f)
            await self._ctl.call("report_flight_dump", {
                "source": data.get("source") or f"worker-{w.pid}",
                "reason": data.get("reason", ""),
                "ts": data.get("ts"), "path": path,
                "sticky": data.get("sticky") or {},
                "events": (data.get("events") or [])[-200:]})
        except (OSError, ValueError, RpcError):
            pass

    # --------------------------------------------------------- worker pool
    def _spawn_worker(self, runtime_env: Optional[Dict] = None) -> None:
        env = dict(os.environ)
        env.update(self.config.env_overrides())
        # A chip belongs to one process: on a node that has chips a
        # worker starts kept off them, whatever its tasks import, and
        # only a lease that grants chips lifts that (chip_lease.py).
        chip_lease.guard_spawn_env(env, int(self.total.get("TPU")))
        # One compile cache for every process of the runtime, placed
        # from outside or at the fixed default.
        compile_cache.ensure_env(env)
        env_hash = ""
        if runtime_env:
            env_hash = runtime_env.get("hash", "")
            env.update(runtime_env.get("env_vars", {}))
            env["RT_RUNTIME_ENV"] = json.dumps(runtime_env)
        # Control-plane vars LAST: user env_vars must never override the
        # addresses the worker needs to register at all.
        env.update({
            "RT_SESSION_NAME": self.session,
            "RT_CONTROLLER_ADDR": self.controller_addr,
            "RT_AGENT_ADDR": self.server.address,
            "RT_NODE_ID": self.node_id.hex(),
            "RT_OBJECT_STORE_BACKEND": self._store_backend,
            # Startup-phase anchor: the worker stamps its hello with
            # spawn/import/connect durations measured from this fork
            # time (rt_worker_startup_seconds).
            "RT_SPAWN_TS": repr(time.time()),
        })
        self._spawned_total += 1
        log_dir = os.path.join(self.config.session_dir_root, self.session,
                               "logs")
        os.makedirs(log_dir, exist_ok=True)
        log_path = os.path.join(
            log_dir, f"worker-{self.node_id.hex()[:8]}-"
            f"{self._spawned_total}-{time.time():.0f}.log")
        out = open(log_path, "ab")
        # pip envs: spawn the trampoline, which builds/reuses the venv
        # (file-locked, off this event loop) and execs worker_main
        # under the venv python (ref: _private/runtime_env/pip.py —
        # the worker STARTS inside its environment).
        if runtime_env and runtime_env.get("pip"):
            module = "ray_tpu.runtime_env.pip_bootstrap"
        elif runtime_env and runtime_env.get("uv"):
            module = "ray_tpu.runtime_env.uv_bootstrap"
        else:
            module = "ray_tpu.core.worker_main"
        try:
            proc = subprocess.Popen(
                [sys.executable, "-u", "-m", module],
                env=env, stdout=out, stderr=subprocess.STDOUT,
                start_new_session=True)
        finally:
            # The starting/_starting_by_env bookkeeping happens only
            # AFTER a successful fork: a raising Popen (EAGAIN/ENOMEM
            # under exactly the fork storms the pool creates) must
            # not permanently inflate the spawn budgets.
            out.close()
        self._starting_workers += 1
        self._worker_log_paths = getattr(self, "_worker_log_paths", {})
        self._worker_log_paths[proc.pid] = log_path
        self._spawned_procs.append(proc)
        self._pending_spawns = getattr(self, "_pending_spawns", {})
        self._pending_spawns[proc.pid] = (proc, env_hash)
        by_env = getattr(self, "_starting_by_env", None)
        if by_env is None:
            by_env = self._starting_by_env = {}
        by_env[env_hash] = by_env.get(env_hash, 0) + 1

    def _starting_done(self, env_hash: str) -> None:
        self._starting_workers = max(0, self._starting_workers - 1)
        by_env = getattr(self, "_starting_by_env", {})
        if env_hash in by_env:
            by_env[env_hash] = max(0, by_env[env_hash] - 1)

    async def register_worker(self, p):
        pending = getattr(self, "_pending_spawns", {}).pop(
            p["pid"], (None, ""))
        if self._draining:
            # A spawn that raced the drain decision: this worker can
            # never be adopted (grants are refused) — kill it now
            # instead of parking a useless process through the grace.
            self._starting_done(pending[1])
            try:
                if pending[0] is not None:
                    pending[0].kill()
                else:
                    os.kill(p["pid"], signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
            return {"ok": False, "draining": True,
                    "node_id": self.node_id}
        w = WorkerEntry(
            worker_id=p["worker_id"], addr=p["addr"], pid=p["pid"],
            proc=pending[0], state="idle", env_hash=pending[1],
            log_path=getattr(self, "_worker_log_paths",
                             {}).get(p["pid"], ""))
        self.workers[w.worker_id] = w
        self._starting_done(w.env_hash)
        self._idle_q.append(w)
        self._worker_ready.set()
        for phase, dt in (p.get("phases") or {}).items():
            try:
                self._startup_hist.observe(float(dt),
                                           tags={"phase": str(phase)})
            except (TypeError, ValueError):
                pass
        self._kick_scheduler()
        return {"ok": True, "node_id": self.node_id}

    async def worker_heartbeat(self, p):
        return {"ok": True}

    async def report_backlog(self, p):
        """Owner-side per-scheduling-key backlog report (notify; ref:
        ReportWorkerBacklog in normal_task_submitter.h) — folded into
        the heartbeat's demand vector with a freshness TTL so demand
        from a dead owner ages out."""
        backlogs = getattr(self, "_owner_backlogs", None)
        if backlogs is None:
            backlogs = self._owner_backlogs = {}
        key = (p.get("owner"), p.get("key"))
        if not p.get("backlog"):
            backlogs.pop(key, None)
        else:
            backlogs[key] = (dict(p["resources"]),
                             int(p["backlog"]), time.time())
        return {"ok": True}

    def _demand_vector(self):
        """This node's current unsatisfied demand: queued lease
        requests + owner-reported backlogs + autoscaler-held
        infeasible demands (the vector the heartbeat advertises and
        `rt list leases` exposes for diagnosis)."""
        demands = [dict(req.payload["resources"])
                   for req in self.pending][:100]
        demands += self._backlog_demands()
        demands += list(getattr(self, "_infeasible", []))[:100]
        return demands

    def _backlog_demands(self, cap: int = 100):
        """Fresh owner backlogs as a demand list for the autoscaler."""
        backlogs = getattr(self, "_owner_backlogs", {})
        now = time.time()
        out = []
        for key, (res, n, ts) in list(backlogs.items()):
            if now - ts > 5.0:
                backlogs.pop(key, None)
                continue
            out.extend([dict(res)] * min(n, 20))
            if len(out) >= cap:
                break
        return out[:cap]

    async def report_task_events(self, p):
        """Relay worker task events to the controller sink (workers have
        no persistent controller connection; the agent does)."""
        try:
            await self._ctl.call("task_events", {"events": p["events"]})
        except RpcError:
            pass
        return {"ok": True}

    async def report_metrics(self, p):
        try:
            await self._ctl.call("report_metrics", p)
        except RpcError:
            pass
        return {"ok": True}

    async def report_spans(self, p):
        """Relay a worker's drained span ring to the controller's span
        sink (workers have no persistent controller connection; this
        is the same relay report_task_events rides)."""
        p.setdefault("node_id", self.node_id.hex())
        try:
            await self._ctl.call("report_spans", p)
        except RpcError:
            pass
        return {"ok": True}

    async def jax_profile_workers(self, p):
        """Fan an on-demand jax.profiler capture out to every live
        worker on this node (ref: the reference dashboard's
        profile_manager; here the capture runs in-process on the
        worker and the artifact path is reported back through the
        controller so `rt profile --jax` can list it cluster-wide)."""
        req = {"duration_s": p.get("duration_s", 3.0),
               "log_dir": p.get("log_dir"), "force": p.get("force"),
               "python_tracer": bool(p.get("python_tracer"))}

        async def _one(w):
            cli = RpcClient(w.addr, tag="jaxprof")
            try:
                r = await cli.call("jax_profile", req)
            except RpcError as e:
                r = {"ok": False, "error": str(e)}
            finally:
                await cli.close()
            return {"pid": w.pid, "worker_id": w.worker_id.hex(), **r}

        results = await asyncio.gather(
            *[_one(w) for w in list(self.workers.values())])
        for r in results:
            if r.get("ok") and r.get("path"):
                try:
                    await self._ctl.call("report_profile", {
                        "source": f"worker-{self.node_id.hex()[:8]}"
                                  f"-{r['pid']}",
                        "kind": "jax", "path": r["path"],
                        "node_id": self.node_id.hex(),
                        "ts": time.time()})
                except RpcError:
                    pass
        return {"ok": True, "node_id": self.node_id.hex(),
                "results": list(results)}

    def _host_cpu_util(self) -> float:
        """Host CPU utilization since the previous sample, from
        /proc/stat deltas (ref: dashboard/modules/reporter/
        reporter_agent.py psutil.cpu_percent; /proc keeps the agent
        dependency-free)."""
        try:
            with open("/proc/stat") as f:
                parts = f.readline().split()[1:]
            vals = [int(x) for x in parts[:8]]
        except (OSError, ValueError):
            return 0.0
        total = sum(vals)
        idle = vals[3] + (vals[4] if len(vals) > 4 else 0)
        prev = getattr(self, "_prev_cpu_sample", None)
        self._prev_cpu_sample = (total, idle)
        if prev is None or total <= prev[0]:
            return 0.0
        dt = total - prev[0]
        return max(0.0, min(1.0, 1.0 - (idle - prev[1]) / dt))

    def _node_metrics_snapshot(self) -> List[Dict]:
        n_obj, used, cap = self.directory.stats()
        spill = self.directory.spill_stats()
        states: Dict[str, int] = {}
        for w in self.workers.values():
            states[w.state] = states.get(w.state, 0) + 1
        pool_idle, _starting, _leased = self._pool_counts("")
        # The agent's own registry carries rt_worker_startup_seconds
        # (the only registry metric in this process) — ship it with
        # the node snapshot so `rt telemetry` sees the phase
        # histogram without a separate reporting channel.  Loop-lag
        # quantiles and per-method RPC handler stats ride the same
        # snapshot (control-plane introspection, util/hotpath.py).
        from ..util.metrics import registry

        lag = getattr(self, "_loop_lag", None)
        extra = (lag.metric_snaps() if lag is not None else []) \
            + self.server.stats.metric_snaps()
        return list(registry().snapshot()) + extra + [
            {"name": "rt_worker_pool_idle", "kind": "gauge",
             "description": "Prestarted idle workers ready for "
                            "adoption (default runtime env).",
             "series": [{"tags": {}, "value": pool_idle}]},
            {"name": "rt_worker_pool_target", "kind": "gauge",
             "description": "Prestart pool target size.",
             "series": [{"tags": {},
                         "value": self._prestart_target()}]},
            {"name": "rt_worker_adoptions_total", "kind": "counter",
             "description": "Lease grants served by adopting a warm "
                            "pooled worker (cumulative).",
             "series": [{"tags": {}, "value": self._pool_adoptions}]},
            {"name": "rt_worker_cold_spawn_total", "kind": "counter",
             "description": "Lease grants that had to wait for a "
                            "worker process spawn (cumulative).",
             "series": [{"tags": {},
                         "value": self._pool_cold_spawns}]},
        ] + [
            {"name": "rt_node_cpu_util", "kind": "gauge",
             "description": "Host CPU utilization (0-1).",
             "series": [{"tags": {},
                         "value": self._host_cpu_util()}]},
            {"name": "rt_node_mem_util", "kind": "gauge",
             "description": "Host memory utilization (0-1).",
             "series": [{"tags": {},
                         "value": self._memory_usage_fraction()}]},
            {"name": "rt_node_workers", "kind": "gauge",
             "description": "Worker processes by state.",
             "series": [{"tags": {"state": s}, "value": v}
                        for s, v in states.items()]},
            {"name": "rt_node_leases_active", "kind": "gauge",
             "description": "Granted worker leases.",
             "series": [{"tags": {}, "value": len(self.leases)}]},
            {"name": "rt_node_leases_pending", "kind": "gauge",
             "description": "Queued lease requests.",
             "series": [{"tags": {}, "value": len(self.pending)}]},
            {"name": "rt_node_object_store_bytes", "kind": "gauge",
             "description": "Local shared-memory store usage.",
             "series": [{"tags": {"kind": "used"}, "value": used},
                        {"tags": {"kind": "capacity"}, "value": cap}]},
            {"name": "rt_node_objects", "kind": "gauge",
             "description": "Objects in the local store.",
             "series": [{"tags": {}, "value": n_obj}]},
            {"name": "rt_node_resources_available", "kind": "gauge",
             "description": "Schedulable resources available.",
             "series": [{"tags": {"resource": k}, "value": v}
                        for k, v in self.available.amounts.items()]},
            # Object-plane spill counters: these previously died
            # in-process (visible only via the store_stats RPC nobody
            # polls); as metrics they ride the heartbeat into
            # `rt telemetry` / Prometheus.
            {"name": "rt_object_spilled_bytes", "kind": "gauge",
             "description": "Bytes currently spilled to disk by the "
                            "local object store.",
             "series": [{"tags": {},
                         "value": spill["spilled_bytes"]}]},
            {"name": "rt_object_spill_total", "kind": "counter",
             "description": "Objects spilled to disk (cumulative).",
             "series": [{"tags": {}, "value": spill["spill_count"]}]},
            {"name": "rt_object_restore_total", "kind": "counter",
             "description": "Spilled objects restored into shm "
                            "(cumulative).",
             "series": [{"tags": {},
                         "value": spill["restore_count"]}]},
        ]

    def _max_workers(self) -> int:
        cap = self.config.worker_pool_max_workers
        if cap > 0:
            return cap
        return max(int(self.total.get("CPU")) * 4, 16)

    async def _acquire_worker(self, runtime_env: Optional[Dict] = None,
                              fresh: bool = False
                              ) -> Optional[WorkerEntry]:
        # Spawns are bounded by live demand (waiting acquirers), not by the
        # wake-up rate — otherwise every near-miss wake-up forks another
        # interpreter and a 1-core host death-spirals.  Both counters are
        # per runtime-env hash: a worker warming up for env A must not
        # satisfy the spawn budget of a request for env B.
        want = (runtime_env or {}).get("hash", "")
        if want:
            # Remember the env so the prestart pool can keep it warm
            # (and can re-spawn workers INSIDE it after adoptions).
            self._env_specs[want] = dict(runtime_env or {})
            self._env_last_used[want] = time.time()
        acq = getattr(self, "_acquirers_by_env", None)
        if acq is None:
            acq = self._acquirers_by_env = {}
        acq[want] = acq.get(want, 0) + 1
        t0 = asyncio.get_event_loop().time()
        deadline = t0 + self.config.worker_start_timeout_s
        first_pass = True
        try:
            def usable(w: WorkerEntry) -> bool:
                return w.env_hash == want and not (fresh and w.recycled)

            while True:
                match = next((w for w in self._idle_q if usable(w)),
                             None)
                if match is not None:
                    self._idle_q.remove(match)
                    if match.state == "idle":
                        if first_pass or match.recycled:
                            # Warm path: the worker either existed
                            # before the request (pool hit) or was
                            # handed back by a finishing lease — no
                            # fork was paid either way.
                            self._pool_adoptions += 1
                        else:
                            # Waited out a real process spawn.
                            self._note_cold_spawn()
                        self._startup_hist.observe(
                            asyncio.get_event_loop().time() - t0,
                            tags={"phase": "adopt"})
                        self._kick_refill()
                        return match
                    continue
                first_pass = False
                starting = getattr(self, "_starting_by_env", {}) \
                    .get(want, 0)
                # Actor-dedicated workers live outside the pool cap —
                # the cap bounds the REUSABLE task pool; actors scale
                # to memory (OOM monitor guards), matching the
                # reference where maximum_startup_concurrency limits
                # spawn rate, not actor count (ref: worker_pool.cc).
                active = sum(1 for w in self.workers.values()
                             if w.state != "actor") \
                    + self._starting_workers
                if starting < acq[want]:
                    if active >= self._max_workers():
                        # Pool full of workers this request cannot
                        # use (other env, or recycled when it needs a
                        # fresh one): retire an idle one to make room
                        # (ref: worker_pool.cc idle-worker eviction on
                        # env mismatch).
                        victim = next((w for w in self._idle_q
                                       if not usable(w)), None)
                        if victim is not None:
                            self._idle_q.remove(victim)
                            await self._retire_worker(victim)
                            active -= 1
                    if active < self._max_workers():
                        self._spawn_worker(runtime_env)
                self._worker_ready.clear()
                remaining = deadline - asyncio.get_event_loop().time()
                if remaining <= 0:
                    return None
                try:
                    await asyncio.wait_for(self._worker_ready.wait(),
                                           remaining)
                except asyncio.TimeoutError:
                    return None
        finally:
            acq[want] -= 1

    async def _retire_worker(self, w: WorkerEntry) -> None:
        w.state = "dead"
        self.workers.pop(w.worker_id, None)
        try:
            cli = RpcClient(w.addr, connect_timeout=2.0)
            await asyncio.wait_for(cli.call("exit", {}), timeout=5.0)
            await cli.close()
        except (RpcError, asyncio.TimeoutError, OSError):
            if w.proc is not None:
                w.proc.terminate()

    # ------------------------------------------------ warm prestart pool
    def _prestart_target(self) -> int:
        n = self.config.worker_prestart
        if n < 0:
            # Auto: the node's CPUs — bounded by the PHYSICAL core
            # count, not just the declared resource total (test
            # clusters declare num_cpus=4 on 1-core hosts; prestarting
            # more processes than cores only adds fork contention).
            n = min(int(self.total.get("CPU")), os.cpu_count() or 1)
        return max(0, min(n, self._max_workers()))

    def _prestart_burst(self) -> int:
        n = self.config.worker_prestart_burst
        if n <= 0:
            n = max(2, int(self.total.get("CPU")))
        return n

    def _note_cold_spawn(self) -> None:
        """A lease had to wait for a worker spawn (pool miss/empty):
        the fallback the prestart pool exists to avoid.  Windowed for
        the doctor's pool-exhaustion check."""
        self._pool_cold_spawns += 1
        now = time.time()
        self._cold_spawn_ts.append(now)
        if len(self._cold_spawn_ts) > 1024:
            del self._cold_spawn_ts[:512]

    def _cold_spawns_in_window(self, window_s: float = 60.0) -> int:
        cutoff = time.time() - window_s
        return sum(1 for ts in self._cold_spawn_ts if ts >= cutoff)

    def _kick_refill(self) -> None:
        self._refill_wakeup.set()

    def _pool_counts(self, env_hash: str) -> Tuple[int, int, int]:
        """(idle, starting, leased) non-actor workers of one env hash."""
        idle = sum(1 for w in self._idle_q if w.env_hash == env_hash
                   and w.state == "idle")
        starting = getattr(self, "_starting_by_env", {}) \
            .get(env_hash, 0)
        leased = sum(1 for w in self.workers.values()
                     if w.state == "leased" and w.env_hash == env_hash)
        return idle, starting, leased

    async def _prestart_refill_loop(self) -> None:
        """Keep the prestart pool at target: kicked after every
        adoption, and ticking on ``worker_prestart_refill_ms`` to heal
        losses (worker death, env churn).  The refill respects the
        drain state — a DRAINING node's pool is killed, not warmed."""
        period = max(self.config.worker_prestart_refill_ms, 10) / 1000.0
        # Boot warmup: let the agent finish registration/heartbeat
        # setup before forking the first prestart wave — the pool is
        # a steady-state optimization, not a boot-path dependency
        # (and on small shared hosts a fork herd at agent start
        # races the agent's own ready handshake for CPU).
        try:
            await asyncio.wait_for(self._shutdown.wait(), 1.0)
            return
        except asyncio.TimeoutError:
            pass
        while not self._shutdown.is_set():
            try:
                await asyncio.wait_for(self._refill_wakeup.wait(),
                                       period)
            except asyncio.TimeoutError:
                pass
            self._refill_wakeup.clear()
            if self._shutdown.is_set() or self._draining:
                continue
            try:
                self._refill_pool_once()
            except Exception as e:  # noqa: BLE001 — loop must survive
                # A failed fork (EAGAIN/ENOMEM under load) costs one
                # tick, never the loop: a dead refill loop would
                # silently turn every future creation into a cold
                # spawn for the agent's lifetime.
                logger.warning("prestart refill failed: %r", e)

    def _refill_pool_once(self) -> None:
        target = self._prestart_target()
        if target <= 0:
            return
        now = time.time()
        # Expire stale warm envs: drop their specs AND retire their
        # already-prestarted idle workers — default-env requests can
        # never adopt a mismatched env hash, so without this the
        # orphaned interpreters would hold RSS (and count against
        # max_workers room) for the agent's lifetime.
        ttl = self.config.worker_prestart_env_ttl_s
        for h in [h for h, ts in self._env_last_used.items()
                  if now - ts > ttl]:
            self._env_last_used.pop(h, None)
            self._env_specs.pop(h, None)
            for w in [w for w in self._idle_q
                      if w.env_hash == h and w.state == "idle"]:
                self._idle_q.remove(w)
                spawn_task(self._retire_worker(w))
        targets = warm_env_targets(now, target, self._env_last_used,
                                   ttl)
        pending = len(getattr(self, "_pending_spawns", {}))
        burst = self._prestart_burst()
        active = sum(1 for w in self.workers.values()
                     if w.state != "actor") + self._starting_workers
        for env_hash, env_target in targets.items():
            idle, starting, leased = self._pool_counts(env_hash)
            n = pool_plan(
                target=env_target, idle=idle, starting=starting,
                leased=leased, pending_spawns=pending, burst=burst,
                max_workers=self._max_workers(), active=active,
                draining=self._draining)
            renv = self._env_specs.get(env_hash) if env_hash else None
            for _ in range(n):
                self._spawn_worker(renv)
                pending += 1
                active += 1

    def _kill_prestart_pool(self) -> None:
        """DRAINING: idle pooled workers are pure warmth — kill them
        immediately so the grace window's CPU goes to migration work,
        and reap in-flight prestart spawns on arrival (the reap loop
        handles those when they register post-drain via _try_grant's
        refusal; unregistered ones die with the agent)."""
        idle, self._idle_q = self._idle_q, []
        for w in idle:
            w.state = "dead"
            self.workers.pop(w.worker_id, None)
            try:
                if w.proc is not None:
                    w.proc.kill()
                else:
                    os.kill(w.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
        if idle:
            logger.info("drain: killed %d prestarted idle worker(s)",
                        len(idle))

    def _pool_stats_snapshot(self) -> Dict[str, Any]:
        idle, starting, leased = self._pool_counts("")
        idle_all = sum(1 for w in self._idle_q if w.state == "idle")
        hist_counts: Dict[str, int] = {}
        for s in self._startup_hist._snapshot().get("series", []):
            phase = (s.get("tags") or {}).get("phase", "?")
            hist_counts[phase] = int(s.get("hist", {}).get("count", 0))
        return {"node_id": self.node_id.hex(),
                "target": self._prestart_target(),
                "idle": idle, "idle_all": idle_all,
                "starting": starting, "leased": leased,
                "pending_spawns": len(getattr(self, "_pending_spawns",
                                              {})),
                "adoptions": self._pool_adoptions,
                "cold_spawns": self._pool_cold_spawns,
                "cold_spawns_60s": self._cold_spawns_in_window(),
                "spawned_total": self._spawned_total,
                "warm_envs": sorted(self._env_last_used),
                "draining": self._draining,
                "startup": hist_counts}

    async def pool_stats(self, _p=None):
        """The prestart pool's books (scale benches, `rt doctor`,
        tests): adoption vs cold-spawn counters, occupancy, and
        startup-phase sample counts."""
        return self._pool_stats_snapshot()

    # -------------------------------------- batched actor-started relay
    async def report_actor_started(self, p):
        """Relay a worker's actor hello to the controller, COALESCED:
        a creation fan-out (100 serve replicas, an RL env-runner
        fleet) becomes a handful of bulk ``actors_started`` RPCs on
        one persistent connection instead of a fresh controller dial
        per actor.  The worker still gets its per-actor reply (the
        kill-during-creation verdict rides it)."""
        fut: asyncio.Future = asyncio.get_event_loop().create_future()
        self._actor_started_buf.append((p, fut))
        if not self._actor_started_scheduled:
            self._actor_started_scheduled = True
            asyncio.get_event_loop().call_later(
                0.005, lambda: spawn_task(self._flush_actor_started()))
        return await fut

    async def _flush_actor_started(self) -> None:
        self._actor_started_scheduled = False
        items, self._actor_started_buf = self._actor_started_buf, []
        if not items:
            return
        try:
            r = await self._ctl.call(
                "actors_started", {"items": [p for p, _f in items]})
            results = r.get("results") or []
        except (RpcError, RemoteCallError) as e:
            # BOTH transport loss and a controller-side handler error
            # must resolve the futures — an escaped exception here
            # would leave every worker in the batch awaiting its
            # hello reply forever.
            for _p, fut in items:
                if not fut.done():
                    fut.set_exception(RpcError(
                        f"actor-started relay failed: {e}"))
            return
        for (_p, fut), res in zip(items, results):
            if not fut.done():
                fut.set_result(res if res is not None
                               else {"ok": False})
        # Length mismatch (controller bug): fail the unanswered rest.
        for _p, fut in items[len(results):]:
            if not fut.done():
                fut.set_exception(RpcError(
                    "actors_started reply shorter than request"))

    # ----------------------------------------------------------- scheduling
    def _kick_scheduler(self) -> None:
        spawn_task(self._drain_pending())

    async def _drain_pending(self) -> None:
        # FIFO with head-of-line skip for infeasible-now requests.
        still: List[_PendingLease] = []
        pending, self.pending = self.pending, []
        for req in pending:
            if req.future.done():
                continue
            granted = await self._try_grant(req.payload)
            if req.future.done():
                # Cancelled while we were granting (cancel_lease_request
                # resolved the future mid-await): give the lease back.
                if granted is not None:
                    lease = self.leases.get(granted["lease_id"])
                    if lease is not None:
                        self._release_lease(lease)
                continue
            if granted is None:
                still.append(req)
            else:
                req.future.set_result(granted)
        self.pending.extend(still)

    def _bundle_for(self, payload) -> Optional[_Bundle]:
        pg_id = payload.get("pg_id")
        if pg_id is None:
            return None
        idx = payload.get("bundle_index", -1)
        if idx >= 0:
            return self.bundles.get((pg_id, idx))
        for (bpid, _bidx), b in self.bundles.items():
            if bpid == pg_id and b.committed and \
                    b.resources.subtract(b.in_use).covers(
                        ResourceSet(payload["resources"])):
                return b
        return None

    def _job_usage_local(self) -> Dict[str, Dict[str, float]]:
        """Per-internal-job resource usage of this node's plain leases
        (PG-bound leases excluded: their bundles are accounted at the
        controller, and counting both would double-charge quotas)."""
        out: Dict[str, Dict[str, float]] = {}
        for lease in self.leases.values():
            if lease.pg_id is not None or not lease.job_id:
                continue
            acc = out.setdefault(lease.job_id, {})
            for k, v in lease.resources.amounts.items():
                acc[k] = acc.get(k, 0.0) + v
        return out

    def _quota_refuses(self, payload) -> bool:
        """Lease-grant-time quota enforcement: True when granting this
        plain lease would run its job over quota — the request stays
        QUEUED and grants as soon as the job's usage drops.  Usage =
        the controller's cluster-wide view minus what this node
        reported into it, plus this node's live books (so back-to-back
        local grants inside one heartbeat period can't overshoot)."""
        if payload.get("pg_id") is not None:
            return False  # bundle capacity was quota-charged at admission
        job_hex = payload.get("job_id") or ""
        view = self._job_view.get(job_hex)
        if view is None or not view.get("quota"):
            return False
        from ..util import multitenant

        used = multitenant.overlay_usage(
            view.get("used") or {},
            self._job_usage_reported.get(job_hex, {}),
            self._job_usage_local().get(job_hex, {}))
        return multitenant.quota_exceeded(view["quota"], used,
                                          dict(payload["resources"]))

    async def _try_grant(self, payload) -> Optional[Dict]:
        # A draining node grants NOTHING — not even queued requests
        # that predate the drain (they are redirected by _begin_drain)
        # or actor restarts (the controller retries on a live node).
        if self._draining:
            return None
        if self._quota_refuses(payload):
            return None  # over quota: stay queued until usage drops
        # Reserve resources synchronously (no awaits) so concurrent grant
        # attempts can't double-spend, then await a worker and refund on
        # failure.
        demand = ResourceSet(dict(payload["resources"]))
        bundle = self._bundle_for(payload)
        if payload.get("pg_id") is not None:
            if bundle is None or not bundle.committed:
                return None  # bundle not ready yet; stay queued
            if not bundle.resources.subtract(bundle.in_use).covers(demand):
                return None
            bundle.in_use = bundle.in_use.add(demand)
        elif not self.available.covers(demand):
            return None
        else:
            self.available = self.available.subtract(demand)
        # Chip ids come from one host-wide ledger regardless of PG binding
        # (bundles reserve TPU *counts*; the ids are assigned at lease
        # time so TPU_VISIBLE_CHIPS isolation always holds).
        chip_ids: List[int] = []
        n_tpu = int(demand.get("TPU"))

        def _refund():
            if bundle is not None:
                bundle.in_use = bundle.in_use.subtract(demand)
            else:
                self.available = self.available.add(demand)
                self._clamp_available()
            self.free_chips.extend(chip_ids)

        if n_tpu > 0:
            if len(self.free_chips) < n_tpu:
                chip_ids = []
                _refund()
                return None  # chips pinned by blocked leases; stay queued
            chip_ids = self.free_chips[:n_tpu]
            self.free_chips = self.free_chips[n_tpu:]
        # Chips go to a worker that has never served a lease: a
        # recycled one may have started a JAX backend already, and a
        # backend's devices are fixed for the life of the process.
        w = await self._acquire_worker(payload.get("runtime_env"),
                                       fresh=n_tpu > 0)
        if w is None:
            _refund()
            return None
        owner_tag = ("" if payload.get("is_actor")
                     else payload.get("owner_tag") or "")
        if owner_tag and not self.server.has_peer(owner_tag):
            # The owner's connection vanished while we were granting
            # (e.g. killed mid worker spawn).  Recording the lease now
            # would strand it forever — the conn-lost sweep already ran
            # and found nothing to reclaim.  No await separates this
            # check from the record below, so the sweep and this guard
            # can never both miss.
            _refund()
            self._idle_q.append(w)
            self._worker_ready.set()
            self._kick_scheduler()
            return {"ok": False, "cancelled": True}
        lease = Lease(
            lease_id=next(self._lease_counter), resources=demand, worker=w,
            chip_ids=chip_ids, pg_id=payload.get("pg_id"),
            bundle_index=payload.get("bundle_index", -1),
            owner_tag=owner_tag, granted_ts=time.time(),
            job_id=payload.get("job_id") or "")
        w.state = "actor" if payload.get("is_actor") else "leased"
        w.lease_id = lease.lease_id
        if payload.get("job_id"):
            w.job_id = payload["job_id"]
        if payload.get("actor_id") is not None:
            w.actor_id = payload["actor_id"]
        self.leases[lease.lease_id] = lease
        return {"ok": True, "lease_id": lease.lease_id,
                "worker_addr": w.addr, "worker_id": w.worker_id,
                "chip_ids": chip_ids, "node_id": self.node_id}

    async def request_lease(self, p):
        r = await self._request_lease_inner(p)
        if r is None:  # every branch must answer; never reply None
            logger.error("request_lease fell through for %r", p)
            r = {"ok": False, "error": "internal: no lease decision"}
        return r

    async def _request_lease_inner(self, p):
        """Grant a worker lease, queue, or spill to another node (ref:
        node_manager.cc:1867 HandleRequestWorkerLease +
        hybrid_scheduling_policy.h)."""
        if self._draining:
            # Redirect new work to a live peer when the placement
            # allows it; affinity/PG-bound leases cannot move, so they
            # fail fast and the owner's retry machinery deals with it.
            if p.get("pg_id") is None and not p.get("no_spill"):
                target = await self._pick_remote(
                    ResourceSet(dict(p["resources"])),
                    p.get("strategy", "DEFAULT"), by_total=True)
                if target is not None:
                    return {"ok": False, "retry_at": target}
            return {"ok": False, "error": "node draining"}
        granted = await self._try_grant(p)
        if granted is not None:
            return granted
        demand = ResourceSet(dict(p["resources"]))
        # Spillback decision (not for PG-bound or affinity-bound leases).
        strategy = p.get("strategy", "DEFAULT")
        if p.get("pg_id") is None and not p.get("no_spill") \
                and strategy in ("DEFAULT", "SPREAD"):
            target = await self._pick_remote(demand, strategy)
            if target is not None:
                return {"ok": False, "retry_at": target}
        if not self.total.covers(demand) and p.get("pg_id") is None:
            # This node can never run it.  Infeasibility is a CLUSTER
            # property (ref: cluster_task_manager.h:42 infeasible queue):
            # forward to any node whose TOTAL covers the demand — its
            # available may just be stale in the controller view — and
            # only error when no such node exists.  Affinity-bound and
            # hop-capped leases (no_spill) must NOT be forwarded: running
            # elsewhere would violate the placement constraint.
            if not p.get("no_spill") and strategy in ("DEFAULT", "SPREAD"):
                target = await self._pick_remote(demand, strategy,
                                                 by_total=True)
                if target is not None:
                    return {"ok": False, "retry_at": target}
                if self.config.autoscaling_enabled:
                    # Hold the request and surface it as demand; the
                    # autoscaler bin-packs held demands into new nodes
                    # (ref: cluster_task_manager.h infeasible queue +
                    # autoscaler LoadMetrics).  Re-probe for a capable
                    # node until one joins or the request times out.
                    return await self._await_feasible(p, demand, strategy)
            return {"ok": False,
                    "infeasible": True,
                    "error": f"resources {demand.amounts} can never be "
                             f"satisfied by any alive node "
                             f"(this node total {self.total.amounts})"}
        # Feasible here eventually: queue until resources free up.
        fut: asyncio.Future = asyncio.get_event_loop().create_future()
        self.pending.append(_PendingLease(p, fut))
        timeout = p.get("queue_timeout") or 3600.0
        try:
            return await asyncio.wait_for(fut, timeout)
        except asyncio.TimeoutError:
            return {"ok": False, "error": "lease queue timeout"}

    async def _await_feasible(self, p, demand: ResourceSet,
                              strategy: str):
        rec = dict(demand.amounts)
        infeasible = getattr(self, "_infeasible", None)
        if infeasible is None:
            infeasible = self._infeasible = []
        infeasible.append(rec)
        rid = p.get("request_id")
        holds = getattr(self, "_infeasible_holds", None)
        if holds is None:
            holds = self._infeasible_holds = {}
        if rid:
            holds[rid] = rec
            hold_owners = getattr(self, "_hold_owner_tags", None)
            if hold_owners is None:
                hold_owners = self._hold_owner_tags = {}
            hold_owners[rid] = p.get("owner_tag") or ""
        deadline = asyncio.get_event_loop().time() + \
            (p.get("queue_timeout") or 3600.0)
        try:
            while asyncio.get_event_loop().time() < deadline:
                await asyncio.sleep(0.5)
                if rid and rid not in holds:
                    # cancel_lease_request yanked the hold: stop
                    # advertising demand for a task nobody wants.
                    return {"ok": False, "cancelled": True}
                if self.total.covers(demand):
                    # A hot-added local resource (not typical) — requeue.
                    return {"ok": False, "retry_at": self.server.address}
                target = await self._pick_remote(demand, strategy,
                                                 by_total=True)
                if target is not None:
                    return {"ok": False, "retry_at": target}
            return {"ok": False, "error": "lease queue timeout "
                                          "(demand never became feasible)"}
        finally:
            infeasible.remove(rec)
            if rid:
                holds.pop(rid, None)
                getattr(self, "_hold_owner_tags", {}).pop(rid, None)

    async def _pick_remote(self, demand: ResourceSet,
                           strategy: str,
                           by_total: bool = False) -> Optional[str]:
        """Hybrid policy: stay local under the utilization threshold, else
        pick the best remote with available capacity (ref:
        policy/hybrid_scheduling_policy.h:29-50).  ``by_total`` relaxes
        the filter to nodes whose total capacity covers the demand — used
        for demands this node can never satisfy, where the target should
        queue rather than reject."""
        local_util = self.available.utilization(self.total)
        if not by_total and strategy == "DEFAULT" and \
                not self._draining and \
                local_util < self.config.scheduler_spread_threshold \
                and self.total.covers(demand):
            return None  # queue locally; we're not saturated
        try:
            view = await self._ctl.call("resource_view", {})
        except RpcError:
            return None
        candidates = []
        for nid, info in view.items():
            if nid == self.node_id:
                continue
            avail = ResourceSet(dict(info["available"]))
            total = ResourceSet(dict(info["total"]))
            if (total if by_total else avail).covers(demand):
                candidates.append((avail.utilization(total), str(nid.hex()),
                                   info["agent_addr"]))
        if not candidates:
            return None
        candidates.sort()
        if strategy == "SPREAD":
            return candidates[0][2]
        # DEFAULT: only spill if we cannot serve now and someone can.
        # A DRAINING node can never serve — its free capacity is a
        # mirage (grants are refused), so the redirect must fire even
        # when available covers the demand, or a lightly-loaded
        # draining node hard-fails every request aimed at it.
        if self._draining or not self.available.covers(demand):
            return candidates[0][2]
        return None

    def _release_lease(self, lease: Lease, worker_back: bool = True) -> None:
        if lease.lease_id not in self.leases:
            return
        del self.leases[lease.lease_id]
        bundle = None
        if lease.pg_id is not None:
            bundle = self.bundles.get((lease.pg_id, lease.bundle_index))
            if bundle is None:
                for key, b in self.bundles.items():
                    if key[0] == lease.pg_id and \
                            b.in_use.covers(lease.resources):
                        bundle = b
                        break
        if bundle is not None:
            try:
                bundle.in_use = bundle.in_use.subtract(lease.resources)
            except ValueError:
                bundle.in_use = ResourceSet()
            if lease.blocked:
                # Undo the node-pool CPU credited at block time: the
                # bundle accounting above is the only release a PG
                # lease gets, so the credit would otherwise leak
                # phantom CPU into the pool forever.
                part = self._blockable_part(lease.resources)
                self.available = ResourceSet({
                    **self.available.amounts,
                    "CPU": self.available.get("CPU")
                    - part.get("CPU")})
        elif lease.blocked:
            # CPU was already re-credited at block time; return the rest.
            rest = lease.resources.subtract(
                self._blockable_part(lease.resources))
            self.available = self.available.add(rest)
            self._clamp_available()
        else:
            self.available = self.available.add(lease.resources)
            self._clamp_available()
        w = lease.worker
        w.lease_id = None
        if lease.chip_ids and w.state != "dead":
            # The worker's JAX backend holds these chips for as long as
            # its process lives: retire it, and hand the chips on only
            # once it is gone.
            w.state = "retiring"
            spawn_task(self._return_chips_after_exit(
                w, list(lease.chip_ids)))
        else:
            self.free_chips.extend(lease.chip_ids)
        if worker_back and w.state == "leased":
            w.state = "idle"
            w.actor_id = None
            w.recycled = True
            self._idle_q.append(w)
            self._worker_ready.set()
        self._kick_scheduler()

    async def _return_chips_after_exit(self, w: WorkerEntry,
                                       chip_ids: List[int]) -> None:
        try:
            if w.proc is not None:
                w.proc.kill()
            else:
                os.kill(w.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        deadline = time.monotonic() + 30.0
        while _pid_alive(w) and time.monotonic() < deadline:
            await asyncio.sleep(0.05)
        self.free_chips.extend(chip_ids)
        self._kick_scheduler()

    def _clamp_available(self) -> None:
        for k, cap in self.total.amounts.items():
            if self.available.amounts.get(k, 0.0) > cap:
                self.available.amounts[k] = cap

    def _on_owner_conn_lost(self, tag: str) -> None:
        """A registered peer's connection dropped.  If that peer owns
        leases or queued lease requests, schedule a grace-delayed
        reclamation — a dead owner can never return them, and the
        stranded workers would hold their resources forever."""
        if not tag:
            return
        # Stamp the disconnect time: the lease ledger reports
        # "owner disconnected for N seconds" from THIS moment, not
        # from whenever `rt list leases` first happens to look.
        lost_ts = self._owner_conn_lost_ts
        lost_ts[tag] = time.time()
        if len(lost_ts) > 1024:  # bound under owner churn
            oldest = min(lost_ts, key=lost_ts.get)
            lost_ts.pop(oldest, None)
        owns = any(l.owner_tag == tag for l in self.leases.values()) \
            or any(req.payload.get("owner_tag") == tag
                   for req in self.pending) \
            or tag in getattr(self, "_hold_owner_tags", {}).values()
        watching = getattr(self, "_reclaim_watch", None)
        if watching is None:
            watching = self._reclaim_watch = set()
        if owns and tag not in watching:
            watching.add(tag)
            spawn_task(self._reclaim_owner_leases(tag))

    async def _await_owner_death(self, tag: str,
                                 grace_s: float) -> bool:
        """True once the owner behind ``tag`` is confirmed gone, False
        if it reconnected.  rt-<pid> owners are processes on THIS node
        (only a runtime talking to its local agent uses that tag), so
        their liveness is checked directly — and re-checked on a slow
        cadence while the process lives, because the reclaim trigger is
        edge-based (the connection already dropped; if the owner dies
        later WITHOUT reconnecting, no further event fires).  rt-peer-*
        owners are remote; for them the grace window is the only
        signal, so a transient cross-node drop CAN cost a live owner
        its leased workers — that degrades to the worker_failed path
        (the owner's submit loop resubmits the failed task), a bounded
        retry, versus the forever-leak reclaiming too late would be."""
        local_pid = (int(tag[3:])
                     if tag.startswith("rt-") and tag[3:].isdigit()
                     else None)
        while True:
            await asyncio.sleep(grace_s)
            if self.server.has_peer(tag):
                return False
            if local_pid is None:
                return True
            try:
                os.kill(local_pid, 0)
            except ProcessLookupError:
                return True
            except PermissionError:
                return False  # pid exists (other user): not ours
            if not any(l.owner_tag == tag
                       for l in self.leases.values()):
                return False  # nothing left to watch for
            grace_s = 10.0  # alive local owner: keep watching

    async def _reclaim_owner_leases(self, tag: str,
                                    grace_s: float = 3.0) -> None:
        """After a grace window (a transient reconnect re-registers the
        tag on the owner's next call), free every lease the dead owner
        still holds.  The leased workers are KILLED, not recycled: the
        owner may have had a push in flight, and a worker with orphaned
        work must not re-enter the idle pool (same rationale as
        return_lease's worker_failed path)."""
        try:
            dead = await self._await_owner_death(tag, grace_s)
        finally:
            getattr(self, "_reclaim_watch", set()).discard(tag)
        if not dead:
            return  # owner reconnected; its leases are still live
        # Cancel queued + autoscaler-held lease requests from the dead
        # owner (a held infeasible demand would otherwise keep driving
        # the autoscaler for up to queue_timeout).
        hold_owners = getattr(self, "_hold_owner_tags", {})
        for rid in [r for r, t in list(hold_owners.items())
                    if t == tag]:
            getattr(self, "_infeasible_holds", {}).pop(rid, None)
            hold_owners.pop(rid, None)
        for req in list(self.pending):
            if req.payload.get("owner_tag") == tag \
                    and not req.future.done():
                req.future.set_result({"ok": False, "cancelled": True})
                try:
                    self.pending.remove(req)
                except ValueError:
                    pass
        stale = [l for l in self.leases.values() if l.owner_tag == tag]
        for lease in stale:
            logger.warning(
                "reclaiming lease %s (worker pid %s): owner %s is gone",
                lease.lease_id, lease.worker.pid, tag)
            self._release_lease(lease, worker_back=False)
            w = lease.worker
            w.state = "dead"
            self.workers.pop(w.worker_id, None)
            try:
                if w.proc is not None:
                    w.proc.kill()
                else:
                    os.kill(w.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass

    async def cancel_lease_request(self, p):
        """Yank a queued-but-ungranted lease request (task cancellation;
        ref: node_manager CancelWorkerLease)."""
        rid = p.get("request_id")
        for req in list(self.pending):
            if req.payload.get("request_id") == rid \
                    and not req.future.done():
                req.future.set_result(
                    {"ok": False, "cancelled": True})
                self.pending.remove(req)
                return {"ok": True, "cancelled": True}
        holds = getattr(self, "_infeasible_holds", {})
        if rid in holds:
            # Held in _await_feasible (cluster-infeasible demand waiting
            # for the autoscaler): drop the hold; the waiter notices
            # within its poll tick.
            del holds[rid]
            return {"ok": True, "cancelled": True}
        return {"ok": True, "cancelled": False}

    async def return_lease(self, p):
        lease = self.leases.get(p["lease_id"])
        if lease is not None:
            if p.get("worker_failed"):
                # The owner's push to this worker failed: free the
                # resources but do NOT recycle the worker — kill it so
                # the reap loop confirms death (a wedged-but-alive
                # worker must not re-enter the idle pool).
                self._release_lease(lease, worker_back=False)
                w = lease.worker
                w.state = "dead"
                self.workers.pop(w.worker_id, None)
                try:
                    if w.proc is not None:
                        w.proc.kill()
                    else:
                        os.kill(w.pid, signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass
            else:
                self._release_lease(lease)
        return {"ok": True}

    async def lease_status(self, p):
        lease = self.leases.get(p["lease_id"])
        if lease is None:
            return {"alive": False}
        return {"alive": lease.worker.state != "dead",
                "worker_addr": lease.worker.addr}

    # ------------------------------------------------ lease ledger view
    async def report_lease_pool(self, p):
        """Owner-side pooled-lease state (notify, sweeper cadence):
        per-lease in-flight pipeline depth, so `rt list leases` can
        show how deep each held lease is pipelined — state only the
        owner knows (pushes go owner -> worker directly)."""
        depths = self._owner_lease_depths
        now = time.time()
        owner = p.get("owner")
        for lid, depth in (p.get("leases") or {}).items():
            depths[int(lid)] = (owner, int(depth), now)
        # Prune on the report cadence, not just in list_leases (which
        # only runs when an operator asks): returned leases stop
        # refreshing and would otherwise accumulate forever.
        self._prune_lease_depths(now)
        return {"ok": True}

    def _prune_lease_depths(self, now: float) -> None:
        depths = self._owner_lease_depths
        for lid in [k for k, (_o, _d, ts) in depths.items()
                    if now - ts > 5.0]:
            depths.pop(lid, None)

    async def list_leases(self, _p):
        """The node's lease ledger + demand vector (scheduler
        explainability: what is held, by whom, how deep, how stale —
        the state that previously was only visible in agent logs)."""
        now = time.time()
        depths = self._owner_lease_depths
        self._prune_lease_depths(now)
        # Disconnect AGE per lease: seeded from the connection-lost
        # hook's stamp, so one `rt doctor` run sees the true age — a
        # momentary re-dial must not read as a dead owner, but an
        # owner that died an hour ago must not read as fresh either.
        disc_since = self._owner_disc_since
        lost_ts = self._owner_conn_lost_ts
        leases = []
        for lease in self.leases.values():
            w = lease.worker
            connected = (not lease.owner_tag
                         or self.server.has_peer(lease.owner_tag))
            if connected:
                disc_since.pop(lease.lease_id, None)
                lost_ts.pop(lease.owner_tag, None)
            else:
                disc_since.setdefault(
                    lease.lease_id,
                    lost_ts.get(lease.owner_tag, now))
            ent = {
                "lease_id": lease.lease_id,
                "owner_tag": lease.owner_tag,
                "owner_connected": connected,
                "owner_disconnected_s": (
                    now - disc_since[lease.lease_id]
                    if not connected else 0.0),
                "worker_pid": w.pid,
                "worker_state": w.state,
                "resources": dict(lease.resources.amounts),
                "chip_ids": list(lease.chip_ids),
                "blocked": lease.blocked,
                "pg_id": (lease.pg_id.hex()
                          if lease.pg_id is not None else None),
                "bundle_index": lease.bundle_index,
                "age_s": (now - lease.granted_ts
                          if lease.granted_ts else 0.0),
                # Per-job attribution: the submitted-job id when the
                # heartbeat view can resolve it, else the internal
                # driver job hex.
                "job": (self._job_view.get(lease.job_id, {})
                        .get("job") or lease.job_id[:12]),
            }
            dep = depths.get(lease.lease_id)
            if dep is not None:
                ent["pipeline_depth"] = dep[1]
            leases.append(ent)
        for lid in [k for k in disc_since if k not in self.leases]:
            disc_since.pop(lid, None)  # lease returned/reclaimed
        pending = [{"resources": dict(req.payload["resources"]),
                    "strategy": req.payload.get("strategy", "DEFAULT"),
                    "owner_tag": req.payload.get("owner_tag", ""),
                    "age_s": now - req.enqueue_time}
                   for req in self.pending]
        return {"node_id": self.node_id.hex(),
                "leases": leases, "pending": pending,
                "demand": self._demand_vector(),
                "available": dict(self.available.amounts),
                "total": dict(self.total.amounts),
                # Pool occupancy rides the ledger so `rt doctor`'s
                # pool-exhaustion check needs no extra fan-out.
                "worker_pool": self._pool_stats_snapshot()}

    async def report_collective_entries(self, p):
        """Relay a worker's inflight collective-entry stamps to the
        controller (gang watchdog input; same relay report_spans
        rides)."""
        p.setdefault("node_id", self.node_id.hex())
        try:
            await self._ctl.call("collective_entries", p)
        except RpcError:
            pass
        return {"ok": True}

    # -------------------------------------------- blocked-worker CPU credit
    @staticmethod
    def _blockable_part(resources: ResourceSet) -> ResourceSet:
        """Only CPU is released while blocked in get() — accelerators stay
        assigned (their chips are still mapped into the worker), matching
        the reference releasing only CPU for blocked workers."""
        return ResourceSet({"CPU": resources.get("CPU")})

    async def task_blocked(self, p):
        """A worker blocked in get(): return its CPU so nested tasks can
        schedule (ref: the reference releases CPU for blocked workers in
        local_task_manager).  PG-bound leases credit the NODE pool too:
        a gang whose placement group covers the whole node would
        otherwise starve every non-PG lease forever — e.g. a training
        gang blocked pushing to a result-queue actor that can never
        schedule (the reference likewise releases blocked workers' CPU
        regardless of placement-group binding)."""
        lease = self.leases.get(p["lease_id"])
        if lease is not None and not lease.blocked:
            lease.blocked = True
            self.available = self.available.add(
                self._blockable_part(lease.resources))
            self._clamp_available()
            self._kick_scheduler()
        return {"ok": True}

    async def task_unblocked(self, p):
        lease = self.leases.get(p["lease_id"])
        if lease is not None and lease.blocked:
            lease.blocked = False
            # May oversubscribe briefly; clamped in heartbeat view.
            part = self._blockable_part(lease.resources)
            self.available = ResourceSet({
                **self.available.amounts,
                "CPU": self.available.get("CPU") - part.get("CPU")})
        return {"ok": True}

    # -------------------------------------------------------- object plane
    async def register_object(self, p):
        """Producer-side registration.  The producer's copy is the primary
        copy: pinned until distributed ref counting frees the object, so
        LRU pressure can never delete the only live copy (ref:
        object_lifecycle_manager.h primary-copy pinning)."""
        oid, size = p["object_id"], p["size"]
        if oid in self._early_released:
            # The owner's fast release overtook this registration
            # (different channels): registering now would create a
            # ghost pinned entry nobody will ever delete.
            self._early_released.discard(oid)
            return {"ok": True}
        evicted = self.directory.register(
            oid, size, primary=p.get("primary", True))
        self._queue_loc_update("add", (oid, size))
        for vid in evicted:
            self._queue_loc_update("remove", vid)
        return {"ok": True}

    def _queue_loc_update(self, kind: str, item) -> None:
        """Buffer one ordered location add/remove for the controller;
        a short flush window coalesces a put/release burst into one
        bulk notify (pull discovery polls with >=20 ms backoff, so a
        5 ms publication delay is invisible — but ~4 control frames
        per object put become amortized to ~zero)."""
        self._loc_buf.append((kind, item))
        if not self._loc_flush_scheduled:
            self._loc_flush_scheduled = True
            asyncio.get_event_loop().call_later(0.005, self._loc_flush)

    def _loc_flush(self) -> None:
        self._loc_flush_scheduled = False
        if self._loc_send_inflight or not self._loc_buf:
            # One acked send in flight at a time: concurrent sends
            # could complete out of order across a reconnect and
            # replay an "add" after its "remove" (ghost entry).
            return
        updates, self._loc_buf = self._loc_buf, []
        self._loc_send_inflight = True

        def _reschedule(delay: float) -> None:
            if not self._loc_flush_scheduled:
                self._loc_flush_scheduled = True
                asyncio.get_event_loop().call_later(
                    delay, self._loc_flush)

        async def _send():
            try:
                await asyncio.wait_for(
                    self._ctl.call("update_locations", {
                        "node_id": self.node_id, "updates": updates}),
                    10.0)
            except (RpcError, asyncio.TimeoutError):
                # Controller reconnect window: REQUEUE (ordered, at the
                # head) and retry after a beat — a dropped batch would
                # permanently hide these copies from cross-node gets
                # (plain puts have no lineage to reconstruct from).
                # Duplicate replays are idempotent controller-side.
                self._loc_buf[0:0] = updates
                if len(self._loc_buf) > 100_000:
                    dropped = len(self._loc_buf) - 100_000
                    del self._loc_buf[:dropped]
                    logger.warning(
                        "location-update backlog overflow: dropped %d "
                        "oldest updates during controller outage — "
                        "some copies may stay unpublished", dropped)
                self._loc_send_inflight = False
                _reschedule(0.5)
                return
            self._loc_send_inflight = False
            if self._loc_buf:
                _reschedule(0.005)

        asyncio.ensure_future(_send())

    async def objects_exist(self, p):
        """Bulk local-directory probe (wait() fallback for objects whose
        controller publication failed or lagged)."""
        return {oid: self.directory.lookup(oid) is not None
                for oid in p["object_ids"]}

    async def object_exists(self, p):
        ent = self.directory.lookup(p["object_id"])
        return {"exists": ent is not None,
                "size": ent.size if ent else 0}

    async def pull_object(self, p):
        """Ensure the object is in the local store; returns its size.
        (ref: pull_manager.h:52 — location lookup then chunked fetch.)"""
        oid = p["object_id"]
        ent = self.directory.lookup(oid)
        if ent is not None:
            return await self._local_ready(oid, ent)
        if p.get("fail_fast"):
            # Recovery probes never coalesce: they must answer "gone"
            # immediately, not wait behind a long-polling pull (and a
            # normal pull must not inherit a probe's instant failure).
            r = await self._do_pull(oid, p.get("timeout", 30.0),
                                    fail_fast=True)
            if r.get("ok"):
                self._grant_read_window(oid)
            return r
        inflight = self._pull_inflight.get(oid)
        if inflight is not None:
            result = await asyncio.shield(inflight)
            if result.get("ok"):
                self._grant_read_window(oid)
            return result
        fut = asyncio.get_event_loop().create_future()
        self._pull_inflight[oid] = fut
        try:
            result = await self._do_pull(oid, p.get("timeout", 30.0))
            if not fut.done():
                fut.set_result(result)
            if result.get("ok"):
                self._grant_read_window(oid)
            return result
        except Exception as e:
            if not fut.done():
                fut.set_exception(e)
            raise
        finally:
            self._pull_inflight.pop(oid, None)

    async def _do_pull(self, oid: ObjectID, timeout: float,
                       fail_fast: bool = False) -> Dict:
        """``fail_fast`` returns "no locations" immediately instead of
        polling — the owner uses it to decide whether to reconstruct the
        object from lineage rather than wait out the timeout."""
        deadline = asyncio.get_event_loop().time() + timeout
        delay = 0.02
        while True:
            try:
                loc = await self._ctl.call("locate_object",
                                           {"object_id": oid})
            except RpcError:
                loc = None
            if loc and loc["nodes"]:
                for cand in loc["nodes"]:
                    if cand["node_id"] == self.node_id:
                        continue
                    addr = cand["agent_addr"]
                    cli = self._peer_agents.get(addr)
                    if cli is None or not cli.connected:
                        cli = RpcClient(addr, tag=f"agent-pull-{self.node_id.hex()[:6]}")
                        try:
                            await cli.connect()
                        except RpcError:
                            continue
                        self._peer_agents[addr] = cli
                    size_hint = loc.get("size", 0)
                    chunk = self.config.object_transfer_chunk_bytes
                    try:
                        if size_hint and size_hint > chunk:
                            n = await self._pull_chunked(
                                cli, oid, size_hint, chunk)
                        else:
                            data = await cli.call("fetch_raw",
                                                  {"object_id": oid})
                            if data is None:
                                continue
                            self.store.put_raw(oid, data)
                            n = len(data)
                    except RpcError:
                        continue
                    if n is None:
                        continue
                    # Pulled replica = secondary copy, LRU-evictable.
                    # Publication rides the ordered update queue so it
                    # can never be overtaken by (or overtake) another
                    # path's add/remove for the same oid.
                    evicted = self.directory.register(oid, n)
                    self._queue_loc_update("add", (oid, n))
                    for vid in evicted:
                        self._queue_loc_update("remove", vid)
                    return {"ok": True, "size": n}
            # Re-check local (producer may have just sealed here).
            ent = self.directory.lookup(oid)
            if ent is not None:
                return await self._local_ready(oid, ent)
            if fail_fast and not (loc and loc["nodes"]):
                return {"ok": False, "error": "no locations"}
            if asyncio.get_event_loop().time() > deadline:
                return {"ok": False, "error": "object not found"}
            await asyncio.sleep(delay)
            delay = min(delay * 1.5, 0.5)

    async def _local_ready(self, oid: ObjectID, ent) -> Dict:
        """Finalize a pull that found a local entry: restore from spill
        if needed, grant the read window, build the reply."""
        if ent.spilled:
            ok = await asyncio.get_event_loop().run_in_executor(
                None, self.directory.restore, oid)
            if not ok:
                return {"ok": False, "error": "spilled copy lost"}
        self._grant_read_window(oid)
        return {"ok": True, "size": ent.size}

    def _grant_read_window(self, oid: ObjectID,
                           ttl: float = 10.0) -> None:
        """Short transient read pin after a successful pull: the caller
        maps the segment out-of-band, and under heavy spill churn the
        object must not be re-spilled in that window (otherwise
        concurrent readers thrash restore/spill and starve).  Windows
        allow transient over-capacity; expiry sheds the excess."""
        self.directory.read_pin(oid)
        loop = asyncio.get_event_loop()

        def _expire():
            self.directory.read_unpin(oid)
            n, used, cap = self.directory.stats()
            if used > cap:
                loop.run_in_executor(
                    None, self.directory._shed_pressure, None)

        loop.call_later(ttl, _expire)

    async def _pull_chunked(self, cli, oid: ObjectID, size: int,
                            chunk: int):
        """Assemble a large object from bounded chunk RPCs, then seal it
        locally (ref: pull_manager.h:52 chunked object reads — chunking
        bounds the per-RPC frame, so no giant pickle frame ever crosses
        the wire).  Up to ``pull_parallelism`` chunk fetches ride the
        wire concurrently (a fixed worker pool over the offset sequence
        — the pool size IS the in-flight window, so backpressure is
        structural): the source overlaps its per-chunk store/disk reads
        across executor threads while earlier chunks are in transit,
        instead of paying one RTT + one read per chunk serially.
        Assembly happens in a host buffer, NOT directly in the
        destination segment: on a shared-/dev/shm test topology the
        destination name aliases the source segment, and an in-place
        create would clobber the bytes mid-read.  Returns the byte
        count, or None if the source lost its copy."""
        buf = bytearray(size)
        offsets = iter(range(0, size, chunk))
        lost = False
        failure: Optional[BaseException] = None

        async def _fetch_worker():
            nonlocal lost, failure
            # Plain-iterator next() is atomic per worker turn (no await
            # between take and use), so offsets are claimed exactly once.
            for offset in offsets:
                if lost or failure is not None:
                    return  # a sibling failed: stop claiming chunks
                length = min(chunk, size - offset)
                try:
                    r = await cli.call("fetch_chunk", {
                        "object_id": oid, "offset": offset,
                        "length": length})
                except BaseException as e:  # noqa: BLE001 — re-raised
                    failure = e
                    return
                if r is None or len(r["data"]) < length:
                    lost = True  # copy vanished / source shrank
                    return
                buf[offset:offset + length] = r["data"]

        window = max(1, int(getattr(self.config, "pull_parallelism", 1)))
        n_chunks = (size + chunk - 1) // chunk
        workers = [asyncio.ensure_future(_fetch_worker())
                   for _ in range(min(window, n_chunks))]
        try:
            await asyncio.gather(*workers)
        finally:
            for w in workers:
                w.cancel()
        if failure is not None:
            raise failure  # RpcError -> caller tries the next location
        if lost:
            return None
        self.store.put_raw(oid, memoryview(buf))
        return size

    async def fetch_raw(self, p):
        oid = p["object_id"]
        ent = self.directory.lookup(oid)
        if ent is None:
            return None
        # Transient read pin: the peer's pull must not race local
        # eviction OR spilling.  Disk/shm copies run off the loop.
        self.directory.read_pin(oid)
        try:
            loop = asyncio.get_event_loop()
            if ent.spilled:
                # Serve straight from disk; no need to un-spill locally.
                return await loop.run_in_executor(
                    None, self.directory.read_spilled, oid)
            return await loop.run_in_executor(
                None, self.store.read_raw, oid, ent.size)
        except FileNotFoundError:
            return None
        finally:
            self.directory.read_unpin(oid)

    async def fetch_chunk(self, p):
        """One chunk of an object's packed bytes (ref: pull_manager.h:52
        chunked pulls / ObjectBufferPool) — large objects move as a
        sequence of bounded frames, not one giant one.  Returns
        {"data", "size"} or None if the copy vanished (the puller falls
        back to another location)."""
        oid = p["object_id"]
        ent = self.directory.lookup(oid)
        if ent is None:
            return None
        offset, length = p["offset"], p["length"]
        self.directory.read_pin(oid)
        try:
            loop = asyncio.get_event_loop()
            if ent.spilled:
                data = await loop.run_in_executor(
                    None, self.directory.read_spilled, oid, offset,
                    length)
                if data is None:
                    return None
            else:
                data = await loop.run_in_executor(
                    None, self.store.read_raw_slice, oid, offset,
                    length)
            return {"data": data, "size": ent.size}
        except FileNotFoundError:
            return None
        finally:
            self.directory.read_unpin(oid)

    async def delete_object(self, p):
        self.directory.delete(p["object_id"])

    async def owner_release_local(self, p):
        """Fast-path release from a local owner for a never-shared
        object (plain put whose ref was never pickled): the owner
        already freed the store bytes (eager local free); retire the
        directory entry and the published locations WITHOUT the
        controller owner_release/free_object round trip — no borrower
        or induced borrow can exist for it."""
        oid = p["object_id"]
        if self.directory.delete(oid):
            self._queue_loc_update("remove", oid)
        else:
            # Release overtook the registration (side channel vs main
            # connection): flag it so the late register is dropped
            # instead of resurrecting a ghost entry.  Bounded.
            self._early_released.add(oid)
            while len(self._early_released) > 4096:
                self._early_released.pop()
        return {"ok": True}

    async def store_stats(self, _p):
        n, used, cap = self.directory.stats()
        return {"objects": n, "used_bytes": used, "capacity_bytes": cap,
                **self.directory.spill_stats()}

    async def make_room(self, p):
        """Producer backpressure relief: evict/spill until the caller's
        byte need fits (ref: plasma CreateRequestQueue).  Spill IO is
        blocking — run off the RPC loop."""
        nbytes = int(p.get("bytes", 0))
        evicted = await asyncio.get_event_loop().run_in_executor(
            None, self.directory.make_room, nbytes)
        return {"ok": True, "evicted": len(evicted)}

    # -------------------------------------------------- placement bundles
    async def prepare_bundle(self, p):
        key = (p["pg_id"], p["bundle_index"])
        existing = self.bundles.get(key)
        if existing is not None:
            # Re-prepare of a bundle we still hold (controller retry /
            # reschedule): keep the reservation, don't double-subtract.
            return {"ok": True}
        demand = ResourceSet(dict(p["resources"]))
        if not self.available.covers(demand):
            return {"ok": False}
        self.available = self.available.subtract(demand)
        self.bundles[key] = _Bundle(
            pg_id=p["pg_id"], bundle_index=p["bundle_index"],
            resources=demand)
        return {"ok": True}

    async def commit_bundle(self, p):
        b = self.bundles.get((p["pg_id"], p["bundle_index"]))
        if b is None:
            return {"ok": False}
        b.committed = True
        self._kick_scheduler()
        return {"ok": True}

    async def return_bundle(self, p):
        b = self.bundles.pop((p["pg_id"], p["bundle_index"]), None)
        if b is not None:
            self.available = self.available.add(b.resources)
            self._clamp_available()
            self._kick_scheduler()
        return {"ok": True}

    async def preempt_pg_leases(self, p):
        """Job-preemption enforcement (controller-driven): SIGKILL the
        workers holding leases under this placement group's bundles.
        The deaths flow through the normal reap path — actor_died with
        the worker gone — so the owning trainer sees its gang fail
        AFTER the preemption notice it has been polling, classifies
        the loss as announced, and restarts from the checkpoint-on-
        notice.  Bundle reservations are returned separately by the
        controller's remove_placement_group pass."""
        pg_id = p["pg_id"]
        killed = []
        for lease in list(self.leases.values()):
            if lease.pg_id != pg_id:
                continue
            w = lease.worker
            try:
                if w.proc is not None:
                    w.proc.kill()
                else:
                    os.kill(w.pid, signal.SIGKILL)
                killed.append(w.pid)
            except (ProcessLookupError, PermissionError):
                pass
        if killed:
            logger.warning("preempted %d worker(s) of pg %s (%s)",
                           len(killed), pg_id.hex()[:12],
                           p.get("reason", ""))
        return {"ok": True, "killed": killed}

    # ------------------------------------------------------ actor lifecycle
    async def restart_actor(self, p):
        """Controller asks this node to host a restarted actor."""
        spec = p["spec"]
        granted = await self._try_grant({
            "resources": dict(spec.resources.amounts), "is_actor": True,
            "actor_id": spec.actor_id, "pg_id": None})
        if granted is None:
            return {"ok": False}

        def _undo():
            lease = self.leases.get(granted["lease_id"])
            if lease is not None:
                # Flip back to 'leased' so release re-queues the worker.
                if lease.worker.state == "actor":
                    lease.worker.state = "leased"
                    lease.worker.actor_id = None
                self._release_lease(lease)

        cli = RpcClient(granted["worker_addr"], tag="agent-restart")
        try:
            await cli.connect()
            r = await cli.call("create_actor", {
                "spec": spec, "chip_ids": granted["chip_ids"],
                "lease_id": granted["lease_id"], "is_restart": True})
            await cli.close()
            if not r.get("ok"):
                _undo()
                return {"ok": False}
            return {"ok": True}
        except RpcError:
            _undo()
            return {"ok": False}

    async def report_actor_failure(self, p):
        """Worker-side creation failure path (process still alive)."""
        try:
            await self._ctl.call("actor_died", p)
        except RpcError:
            pass
        return {"ok": True}

    async def kill_worker(self, p):
        target: Optional[WorkerEntry] = None
        if p.get("actor_id") is not None:
            for w in self.workers.values():
                if w.actor_id == p["actor_id"]:
                    target = w
                    break
        elif p.get("worker_id") is not None:
            target = self.workers.get(p["worker_id"])
        if target is not None and target.proc is not None:
            try:
                target.proc.kill()
            except Exception:
                pass
        elif target is not None:
            try:
                os.kill(target.pid, signal.SIGKILL)
            except Exception:
                pass
        return {"ok": target is not None}

    # -------------------------------------------------------------- admin
    async def drain(self, p=None):
        """Enter the DRAINING lifecycle state (operator `rt drain`,
        controller drain_node, or the autoscaler's idle reap).
        ``if_idle`` (the autoscaler's mode) refuses when leases are
        active, closing the race where a task is granted between the
        idle observation and the terminate (ref: DrainRaylet rejection
        path, node_manager.proto:407)."""
        p = p or {}
        if p.get("if_idle") and (self.leases or self.pending):
            return {"ok": False, "busy": True,
                    "leases": len(self.leases)}
        await self._begin_drain(
            reason=p.get("reason") or "drain requested",
            grace_s=p.get("grace_s") or self.config.preemption_grace_s,
            replace=p.get("replace", not p.get("if_idle", False)))
        return {"ok": True, "draining": True,
                "deadline": self._drain_deadline,
                "remaining_s": self._drain_remaining(),
                "node_id": self.node_id.hex()}

    def _drain_remaining(self) -> float:
        """Grace left before this node's drain deadline, in THIS
        host's clock-free terms — the form the deadline crosses hosts
        in (the receiver re-anchors it to its own clock)."""
        if not self._draining or not self._drain_deadline:
            return 0.0
        return max(self._drain_deadline - time.time(), 0.0)

    async def _begin_drain(self, reason: str, grace_s: float,
                           replace: bool = True,
                           shutdown_at_deadline: bool = False) -> None:
        """The drain state machine's single entry point: stop granting,
        stamp the deadline, redirect queued lease requests to live
        peers, and notify the controller immediately (the heartbeat
        would carry it anyway, but the grace window can be seconds —
        every one counts for the checkpoint-on-notice race)."""
        if self._draining:
            return  # already draining; first deadline stands
        self._draining = True
        self._drain_reason = reason
        self._drain_deadline = time.time() + max(grace_s, 0.0)
        self._drain_replace = replace
        # The prestart pool dies with the drain decision: warm idle
        # workers on a node about to die are wasted CPU/RSS, and the
        # refill loop checks _draining before every spawn.
        self._kill_prestart_pool()
        logger.warning("node DRAINING (%s): deadline in %.1fs, "
                       "%d lease(s) held, %d queued request(s)",
                       reason, grace_s, len(self.leases),
                       len(self.pending))
        if shutdown_at_deadline:
            # Preemption-notice drains mirror the real failure: the VM
            # dies at the deadline whether or not we are ready.
            asyncio.get_event_loop().call_later(
                max(grace_s, 0.0), lambda: spawn_task(self.shutdown()))
        # Proactively requeue queued work: resolve each pending lease
        # request with a redirect to a peer that could ever host it,
        # so owners re-request there instead of queueing into a node
        # about to die.  Placement-bound requests stay queued (they
        # cannot move; the controller reschedules the group on death).
        for req in list(self.pending):
            if req.future.done():
                continue
            payload = req.payload
            if payload.get("pg_id") is not None or \
                    payload.get("no_spill"):
                continue
            target = await self._pick_remote(
                ResourceSet(dict(payload["resources"])),
                payload.get("strategy", "DEFAULT"), by_total=True)
            if target is not None and not req.future.done():
                req.future.set_result({"ok": False, "retry_at": target})
                try:
                    self.pending.remove(req)
                except ValueError:
                    pass
        if self._ctl is None:
            return  # SIGTERM before registration: nothing to migrate
        try:
            await self._ctl.call("node_draining", {
                "node_id": self.node_id, "reason": reason,
                "deadline": self._drain_deadline,
                "remaining_s": self._drain_remaining(),
                "replace": replace})
        except RpcError:
            pass  # heartbeat mirrors the state within a period

    async def ping(self, _p):
        return {"ok": True, "node_id": self.node_id}

    async def list_workers(self, _p):
        """Worker inventory (chaos killers + debugging)."""
        return {"workers": [
            {"pid": w.pid, "state": w.state,
             "worker_id": w.worker_id.hex(),
             "actor_id": w.actor_id.hex() if w.actor_id else None}
            for w in self.workers.values()]}

    # ------------------------------------------------------------ log plane
    async def _log_monitor_loop(self) -> None:
        """Tail every worker's log file; publish new lines to the
        controller's worker_logs pubsub channel, job-tagged, so the
        submitting driver can print them (ref: _private/
        log_monitor.py:103 — per-node tailer, redesigned as an agent
        coroutine instead of a separate process)."""
        offsets: Dict[str, int] = {}
        # path -> (pid, worker_id hex, job_id); sticky so a dead
        # worker's final lines still drain with their last-known tags.
        meta: Dict[str, tuple] = {}
        # path -> consecutive no-data ticks while its worker is dead;
        # fully-drained dead entries are dropped so the tail set stays
        # bounded under worker churn.
        idle_dead: Dict[str, int] = {}
        # Dead workers' paths already fully drained: never re-tailed
        # (but still resolvable via _worker_log_paths for fetch).
        drained: set = set()
        while True:
            await asyncio.sleep(0.5)
            batch = []
            advances: List[tuple] = []  # (path, new_offset) on success
            live_pids = set()
            for w in self.workers.values():
                live_pids.add(w.pid)
                if w.log_path:
                    meta[w.log_path] = (w.pid, w.worker_id.hex(),
                                        w.job_id)
            for pid, path in getattr(self, "_worker_log_paths",
                                     {}).items():
                if path not in drained:
                    meta.setdefault(path, (pid, None, None))
            for path, (pid, wid, job) in list(meta.items()):
                try:
                    with open(path, "rb") as f:
                        f.seek(offsets.get(path, 0))
                        data = f.read(256 * 1024)
                except OSError:
                    data = b""
                # Only complete lines; partial tail re-read next tick.
                nl = data.rfind(b"\n") if data else -1
                if nl < 0:
                    if pid not in live_pids:
                        idle_dead[path] = idle_dead.get(path, 0) + 1
                        if idle_dead[path] >= 6:  # ~3s fully drained
                            # Drop from the TAILING set only; the
                            # pid→path mapping stays (it's tiny) so
                            # read_worker_log/list_worker_logs keep
                            # serving dead workers — the file outlives
                            # the process.
                            meta.pop(path, None)
                            offsets.pop(path, None)
                            idle_dead.pop(path, None)
                            drained.add(path)
                            # Bound retained dead entries under churn:
                            # keep the most recent 256 (insertion order
                            # of _worker_log_paths = spawn order).
                            wlp = getattr(self, "_worker_log_paths",
                                          {})
                            if len(drained) > 256:
                                for dpid, dpath in list(wlp.items()):
                                    if len(drained) <= 256:
                                        break
                                    if (dpath in drained
                                            and dpid not in live_pids):
                                        wlp.pop(dpid, None)
                                        drained.discard(dpath)
                    continue
                drained.discard(path)
                idle_dead.pop(path, None)
                lines = data[:nl].decode("utf-8",
                                         "replace").splitlines()
                advances.append((path, offsets.get(path, 0) + nl + 1))
                batch.append({"node_id": self.node_id.hex(),
                              "worker_id": wid, "pid": pid,
                              "job_id": job, "lines": lines})
            if batch:
                try:
                    await self._ctl.call("worker_logs",
                                         {"batch": batch})
                except Exception:
                    # Controller unreachable / handler error: do NOT
                    # advance offsets — the batch re-sends next tick
                    # instead of silently dropping, and ANY exception
                    # must not kill the tailer for the agent's life.
                    continue
                for path, off in advances:
                    offsets[path] = off

    def _worker_by_ref(self, p) -> Optional[WorkerEntry]:
        """Resolve a worker by worker_id hex (prefix ok) or pid."""
        wid, pid = p.get("worker_id"), p.get("pid")
        for w in self.workers.values():
            if pid is not None and w.pid == int(pid):
                return w
            if wid and w.worker_id.hex().startswith(wid):
                return w
        return None

    async def list_worker_logs(self, _p):
        out = []
        known = {w.pid: w for w in self.workers.values()}
        for pid, path in getattr(self, "_worker_log_paths",
                                 {}).items():
            w = known.get(pid)
            try:
                size = os.path.getsize(path)
            except OSError:
                size = -1
            out.append({"pid": pid, "path": path, "size": size,
                        "worker_id": w.worker_id.hex() if w else None,
                        "state": w.state if w else "dead",
                        "job_id": w.job_id if w else None})
        return {"logs": out}

    async def read_worker_log(self, p):
        """Tail a worker's log file — works for DEAD workers too (the
        file outlives the process; ref: dashboard/modules/log/)."""
        path = None
        w = self._worker_by_ref(p)
        if w is not None:
            path = w.log_path
        elif p.get("pid") is not None:
            path = getattr(self, "_worker_log_paths",
                           {}).get(int(p["pid"]))
        if not path:
            return {"ok": False, "error": "unknown worker"}
        max_bytes = int(p.get("max_bytes", 256 * 1024))
        try:
            size = os.path.getsize(path)
            with open(path, "rb") as f:
                f.seek(max(0, size - max_bytes))
                data = f.read(max_bytes)
        except OSError as e:
            return {"ok": False, "error": str(e)}
        return {"ok": True, "path": path,
                "text": data.decode("utf-8", "replace")}

    async def profile_worker(self, p):
        """Sampling-profile a live worker (ref: profile_manager.py:121
        py-spy record — in-process sampler, see util/profiling.py)."""
        w = self._worker_by_ref(p)
        if w is None:
            return {"ok": False, "error": "unknown worker"}
        cli = RpcClient(w.addr, tag="profile")
        try:
            return await cli.call(
                "profile", {"duration_s": p.get("duration_s", 2.0),
                            "hz": p.get("hz", 100.0)},
                )
        finally:
            await cli.close()

    async def stack_worker(self, p):
        w = self._worker_by_ref(p)
        if w is None:
            return {"ok": False, "error": "unknown worker"}
        cli = RpcClient(w.addr, tag="stack")
        try:
            return await cli.call("dump_stack", {})
        finally:
            await cli.close()

    async def node_info(self, _p):
        return {"node_id": self.node_id, "addr": self.server.address,
                "total": dict(self.total.amounts),
                "available": dict(self.available.amounts),
                "workers": len(self.workers),
                "leases": len(self.leases),
                "draining": self._draining,
                "drain_deadline": self._drain_deadline,
                "drain_reason": self._drain_reason}

    async def shutdown(self, _p=None):
        self._shutdown.set()
        if self.is_head and self._store_backend == "pool":
            try:
                self.store.unlink()  # session over: free the tmpfs slab
            except Exception:
                pass
        for w in self.workers.values():
            if w.proc is not None:
                try:
                    w.proc.kill()
                except Exception:
                    pass
            else:
                try:
                    os.kill(w.pid, signal.SIGKILL)
                except Exception:
                    pass
        for proc in self._spawned_procs:
            try:
                proc.kill()
            except Exception:
                pass
        self.directory.clear()
        self.store.close()
        asyncio.get_event_loop().call_soon(
            lambda: spawn_task(self.server.stop()))
        return {"ok": True}

    async def wait_shutdown(self) -> None:
        await self._shutdown.wait()
        await asyncio.sleep(0.1)


def main() -> None:
    import faulthandler

    faulthandler.register(signal.SIGUSR1, all_threads=True)
    parser = argparse.ArgumentParser()
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--session", required=True)
    parser.add_argument("--controller", required=True)
    parser.add_argument("--num-cpus", type=float, default=None)
    parser.add_argument("--num-tpus", type=float, default=None)
    parser.add_argument("--resources", type=str, default="")
    parser.add_argument("--head", action="store_true")
    parser.add_argument("--ready-fd", type=int, default=-1)
    args = parser.parse_args()
    logging.basicConfig(
        level=getattr(logging,
                      os.environ.get("RT_LOG_LEVEL", "INFO").upper(),
                      logging.INFO),
        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    config = RuntimeConfig.from_env()
    custom = {}
    if args.resources:
        import json

        custom = json.loads(args.resources)

    async def _run():
        agent = NodeAgent(
            config, args.session, args.controller,
            num_cpus=args.num_cpus, num_tpus=args.num_tpus,
            custom_resources=custom, is_head=args.head)
        port = await agent.start(args.port)
        if args.ready_fd >= 0:
            os.write(args.ready_fd,
                     f"{agent.server.address} "
                     f"{agent.node_id.hex()}\n".encode())
            os.close(args.ready_fd)
        else:
            print(f"AGENT_ADDRESS={agent.server.address}", flush=True)
        await agent.wait_shutdown()

    try:
        asyncio.run(_run())
    except KeyboardInterrupt:
        pass
    sys.exit(0)


if __name__ == "__main__":
    main()
