"""The controller process — cluster metadata authority.

Role-equivalent to the reference's GCS server (ref:
src/ray/gcs/gcs_server/gcs_server.h:89 and its manager classes): node
membership + health checks, actor directory with restart orchestration,
named actors, an object location directory, a KV store (collective
rendezvous, function table), cursor-based pubsub, and job registration.
Single asyncio process; all state lives on the loop thread so no locks.

Deviation from the reference, on purpose: the object *location* directory
is centralized here rather than owner-distributed — at TPU-host
granularity the directory is small (hosts, not chips, hold objects) and a
single authority removes the owner-failure protocol; lineage-based
reconstruction still lives with the owning worker (see
cluster_runtime.py:_reconstruct_object and its retry bookkeeping).
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import os
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Set, Tuple

from .config import RuntimeConfig
from .ids import ActorID, JobID, NodeID, ObjectID
from .rpc import RpcClient, RpcError, RpcServer, spawn_task

logger = logging.getLogger("ray_tpu.controller")

# Actor lifecycle states (ref: gcs.proto ActorTableData.ActorState).
PENDING = "PENDING"
ALIVE = "ALIVE"
RESTARTING = "RESTARTING"
DEAD = "DEAD"

# Task-state lifecycle tiers for headline-state resolution: terminal
# execution states outrank RUNNING, which outranks every owner-side
# scheduling state (QUEUED/LEASE_REQUESTED/PIPELINED/GRANTED/REQUEUED,
# all tier 1).  Owner and worker clocks are different hosts, so tiers
# — not timestamps — decide across the two planes.
_STATE_TIER = {"FINISHED": 3, "FAILED": 3, "RUNNING": 2}


@dataclass
class NodeEntry:
    node_id: NodeID
    agent_addr: str
    resources_total: Dict[str, float]
    resources_available: Dict[str, float]
    last_heartbeat: float
    alive: bool = True
    labels: Dict[str, str] = field(default_factory=dict)
    is_head: bool = False
    idle_s: float = 0.0                 # autoscaler: node idle duration
    pending_demands: List = field(default_factory=list)
    # Drain plane: set by node_draining / drain_node and refreshed by
    # the agent's heartbeat; drives lease-avoidance (resource_view),
    # the autoscaler's proactive replacement, and doctor's stale-drain
    # check.
    draining: bool = False
    drain_deadline: float = 0.0
    drain_reason: str = ""
    drain_replace: bool = True
    # Prestart-pool occupancy mirrored from the agent heartbeat
    # ({idle, target, adoptions, cold_spawns}) for `rt status` and
    # the dashboard node table.
    worker_pool: Dict = field(default_factory=dict)


@dataclass
class ActorEntry:
    actor_id: ActorID
    state: str
    class_name: str
    method_names: List[str]
    node_id: Optional[NodeID] = None
    worker_addr: str = ""
    name: str = ""
    namespace: str = ""
    restarts_remaining: int = 0
    creation_spec: Any = None          # pickled TaskSpec replayed on restart
    owner_addr: str = ""
    death_reason: str = ""
    detached: bool = False
    max_concurrency: int = 1


class Controller:
    def __init__(self, config: RuntimeConfig, session: str):
        self.config = config
        self.session = session
        self.server = RpcServer()
        self.nodes: Dict[NodeID, NodeEntry] = {}
        self.actors: Dict[ActorID, ActorEntry] = {}
        self.named_actors: Dict[Tuple[str, str], ActorID] = {}
        self.kv: Dict[str, bytes] = {}
        self.kv_list_counts: Dict[str, int] = {}  # kv_append item counts
        self.object_dir: Dict[ObjectID, Dict] = {}  # oid -> {nodes:set,size}
        self.events: Dict[str, List[Tuple[int, Any]]] = {}
        self.events_trimmed_to: Dict[str, int] = {}  # ch -> last trimmed seq
        self.event_seq = 0
        self.event_waiters: List[asyncio.Event] = []
        self.jobs: Dict[int, Dict] = {}
        self.job_counter = 1
        # Multi-tenant job plane: per-submitted-job metadata keyed by
        # the STRING submission id (the `job-...` id the supervisor
        # registers) — priority, optional resource quota, submit time.
        # Distinct from self.jobs, which tracks internal driver
        # registrations; the two link through the driver's RT_JOB_ID
        # (register_job's "tenant" field).
        self.job_plane: Dict[str, Dict] = {}
        # Active preemption notices: job_id -> {deadline, reason, by}.
        # The victim's trainer polls job_preemption_state on its drain
        # cadence; at the deadline _job_preemption_loop enforces by
        # evicting the job's placement groups.
        self.preempting: Dict[str, Dict] = {}
        # Agent-reported plain-lease usage per node: node_hex ->
        # {internal_job_hex: {resource: amount}} (PG-bound leases are
        # excluded — bundle reservations are counted controller-side).
        self._job_usage_by_node: Dict[str, Dict[str, Dict]] = {}
        # Task-event sink (ref: gcs_task_manager.h:86 GcsTaskManager):
        # bounded per-task records for the state API + Chrome-trace
        # timeline export; oldest finished records are dropped first.
        from collections import OrderedDict

        self.task_records: "OrderedDict[str, Dict]" = OrderedDict()
        self.task_events_dropped = 0
        # Hot-path phase sink: sampled task stamp records (sliced into
        # named lifecycle phases by the owner) arriving piggybacked on
        # task_events flushes; `rt hotpath` reads its snapshot.
        from ray_tpu.util.hotpath import Sink as _HotpathSink

        self.hotpath_sink = _HotpathSink()
        # Cluster metrics: latest snapshot per reporting source (ref:
        # metrics agent / opencensus exporter, metric_defs.cc).
        self.metrics_sources: Dict[str, Any] = {}
        # Flight-recorder dumps forwarded by node agents when a worker
        # dies (bounded; newest wins per source).
        self.flight_dumps: "OrderedDict[str, Dict]" = OrderedDict()
        # Cross-process span sink (collectives, train-step phases,
        # serve requests, explicit tracing spans) drained from every
        # worker/driver ring on the heartbeat cadence; merged with
        # task_records by the cluster timeline export.
        from collections import deque as _deque

        self.span_records: "_deque[Dict]" = _deque(
            maxlen=self.config.task_event_buffer_size)
        self.spans_received = 0
        # Slowest-request exemplars per window, fed from finished
        # ingress spans as they arrive — `rt trace` (no argument) and
        # the doctor's find_slow_requests read this instead of
        # re-scanning the whole span sink.
        from ray_tpu.util.reqtrace import ExemplarRing

        self.request_exemplar_ring = ExemplarRing(
            capacity=int(os.environ.get("RT_TRACE_EXEMPLARS", "32")),
            window_s=float(os.environ.get(
                "RT_TRACE_EXEMPLAR_WINDOW_S", "600")))
        # On-demand profiler artifacts (e.g. jax.profiler trace dirs)
        # reported by node agents after an `rt profile --jax` capture.
        self.profile_artifacts: "_deque[Dict]" = _deque(maxlen=64)
        # Gang-watchdog input: per-source inflight collective-entry
        # stamps, REPLACED on every report (an exited op vanishes on
        # the reporter's next tick; a hung one keeps refreshing).
        self.collective_reports: Dict[str, Dict] = {}
        # Autoscaler decision ring: one bounded record per reconcile
        # tick that acted or found unsatisfiable demand — the "why
        # didn't it scale" answer (round-5 demand-blindness weakness).
        self.autoscaler_decisions: "_deque[Dict]" = _deque(maxlen=128)
        self._agent_clients: Dict[NodeID, RpcClient] = {}
        self._placement = None  # PlacementGroupManager, attached in setup
        self._shutdown = asyncio.Event()
        for name in [
            "register_node", "heartbeat", "list_nodes", "resource_view",
            "register_actor", "register_actors", "actor_started",
            "actors_started", "actor_died", "get_actor",
            "lookup_named_actor", "kill_actor", "worker_exited",
            "kv_put", "kv_get", "kv_del", "kv_keys", "kv_append", "kv_list",
            "publish_locations", "remove_locations", "update_locations",
            "locate_object", "locate_objects",
            "free_object", "owner_release", "add_borrower",
            "remove_borrower", "link_induced_borrows",
            "poll_events", "register_job", "finish_job",
            "create_placement_group", "remove_placement_group",
            "get_placement_group", "list_placement_groups",
            "list_actors", "cluster_shutdown", "ping", "drain_node",
            "node_draining",
            "task_events", "hotpath", "list_tasks", "get_task",
            "list_objects",
            "list_jobs", "report_metrics", "metrics_text",
            "metrics_history", "get_load_metrics", "worker_logs",
            "telemetry", "report_flight_dump",
            "report_spans", "list_spans", "report_profile",
            "request_exemplars",
            "explain_task", "collective_entries",
            "report_autoscaler_decision", "doctor_feed",
            "job_register", "jobs_overview", "preempt_job",
            "job_preemption_state",
        ]:
            self.server.register(name, getattr(self, name))

    # ------------------------------------------------------------------ util
    def _publish(self, channel: str, data: Any) -> None:
        self._mark_dirty()  # every table mutation publishes
        self.event_seq += 1
        self.events.setdefault(channel, []).append((self.event_seq, data))
        log = self.events[channel]
        if len(log) > self.config.task_event_buffer_size:
            n = len(log) // 2
            # Remember the highest trimmed seq so slow subscribers whose
            # cursor predates it get an explicit cursor_expired signal
            # (they must resync) instead of silently skipping events.
            self.events_trimmed_to[channel] = log[n - 1][0]
            del log[:n]
        for ev in self.event_waiters:
            ev.set()

    async def _agent(self, node_id: NodeID) -> Optional[RpcClient]:
        node = self.nodes.get(node_id)
        if node is None or not node.alive:
            return None
        cli = self._agent_clients.get(node_id)
        if cli is None or not cli.connected:
            # Short dial window: these are same-DC control-plane dials
            # to agents that already registered.  The default 30s
            # retry loop means every RPC aimed at a just-died (but not
            # yet marked dead) node — kill_actor during a gang
            # teardown, drain_node during a preemption wave — wedges
            # its caller for half a minute.
            cli = RpcClient(node.agent_addr,
                            tag=f"controller->{node_id.hex()[:8]}",
                            connect_timeout=3.0)
            try:
                await cli.connect()
            except RpcError:
                return None
            self._agent_clients[node_id] = cli
        return cli

    # ----------------------------------------------------------------- nodes
    async def register_node(self, p):
        node_id = p["node_id"]
        entry = NodeEntry(
            node_id=node_id, agent_addr=p["agent_addr"],
            resources_total=p["resources"],
            resources_available=dict(p["resources"]),
            last_heartbeat=time.time(), labels=p.get("labels", {}),
            is_head=p.get("is_head", False))
        self.nodes[node_id] = entry
        self._publish("node", {"node_id": node_id, "state": "ALIVE",
                               "agent_addr": entry.agent_addr})
        logger.info("node %s registered (%s)", node_id.hex()[:8],
                    p["agent_addr"])
        return {"ok": True, "session": self.session}

    async def heartbeat(self, p):
        node = self.nodes.get(p["node_id"])
        if node is None:
            return {"ok": False, "reregister": True}
        if not node.alive:
            # The health loop declared this node dead (missed
            # heartbeats — e.g. its event loop starved under a worker
            # fork storm), but the agent is clearly still with us.
            # Without this, a transiently-stalled agent is a PERMANENT
            # zombie: it keeps heartbeating into a row nothing ever
            # resurrects, invisible to scheduling forever.  Route it
            # through the same re-register protocol a restarted
            # controller uses — register_node rebuilds the row alive
            # and the agent republishes its object locations.
            return {"ok": False, "reregister": True}
        node.last_heartbeat = time.time()
        node.resources_available = p.get("available", node.resources_available)
        if "total" in p:
            node.resources_total = p["total"]
        node.idle_s = p.get("idle_s", 0.0)
        node.pending_demands = p.get("pending_demands", [])
        if "worker_pool" in p:
            node.worker_pool = p["worker_pool"] or {}
        if p.get("draining"):
            # The agent's own view is authoritative once it drains;
            # a heartbeat that predates a drain_node RPC must NOT
            # clear controller-marked drain state (drains are one-way
            # until the node dies).  The deadline arrives as REMAINING
            # seconds and is re-anchored to the controller clock here
            # — the stale-drain check compares against this clock, and
            # agent wall time can be arbitrarily skewed.
            node.draining = True
            remaining = p.get("drain_remaining_s")
            if remaining is not None:
                node.drain_deadline = time.time() + float(remaining)
            else:
                node.drain_deadline = p.get("drain_deadline", 0.0)
            node.drain_reason = p.get("drain_reason", "")
            node.drain_replace = p.get("drain_replace", True)
        if "job_usage" in p:
            self._job_usage_by_node[node.node_id.hex()] = \
                p["job_usage"] or {}
        out = {"ok": True}
        view = self._job_quota_view()
        if view:
            # Quota/priority view for lease-grant-time enforcement at
            # the agent: {internal_job_hex: {job, priority, quota,
            # used}}.  Eventually consistent within a heartbeat period
            # — the agent overlays its own since-last-report grants.
            out["jobs"] = view
        return out

    async def get_load_metrics(self, _p):
        """Autoscaler input: per-node utilization + unsatisfied demand
        (ref: autoscaler/_private/load_metrics.py fed from GCS)."""
        nodes = {}
        demands = []
        for n in self.nodes.values():
            if not n.alive:
                continue
            nodes[n.node_id.hex()] = {
                "available": dict(n.resources_available),
                "total": dict(n.resources_total),
                "idle_s": getattr(n, "idle_s", 0.0),
                "is_head": n.is_head,
                "agent_addr": n.agent_addr,
                "draining": n.draining,
                "drain_deadline": n.drain_deadline,
            }
            demands.extend(getattr(n, "pending_demands", []))
            if n.draining and n.drain_replace:
                # Proactive replacement: a draining node's capacity is
                # leaving the cluster — advertise its full shape as
                # demand NOW so the autoscaler starts a replacement
                # during the grace window instead of after the death
                # (idle-timeout drains pass replace=False; replacing a
                # node the scaler itself is reaping would thrash).
                demands.append(dict(n.resources_total))
        pg_demands = []
        if self._placement is not None:
            for entry in self._placement._groups.values():
                if entry.state in ("PENDING", "RESCHEDULING"):
                    pg_demands.append({"bundles": list(entry.bundles),
                                       "strategy": entry.strategy,
                                       "priority": getattr(entry,
                                                           "priority", 0),
                                       "job": getattr(entry, "job", "")})
        return {"nodes": nodes, "pending_demands": demands,
                "pending_placement_groups": pg_demands}

    async def list_nodes(self, _p):
        return [
            {"node_id": n.node_id, "agent_addr": n.agent_addr,
             "alive": n.alive, "resources": n.resources_total,
             "available": n.resources_available, "labels": n.labels,
             "is_head": n.is_head, "draining": n.draining,
             "drain_deadline": n.drain_deadline,
             "drain_reason": n.drain_reason,
             "worker_pool": dict(n.worker_pool)}
            for n in self.nodes.values()
        ]

    async def resource_view(self, _p):
        """Scheduling snapshot used by agents for spillback decisions.
        Draining nodes are excluded — spilling work onto a node about
        to die just converts an announced failure into a surprise
        one."""
        return {
            n.node_id: {"available": n.resources_available,
                        "total": n.resources_total,
                        "agent_addr": n.agent_addr}
            for n in self.nodes.values() if n.alive and not n.draining
        }

    def _resolve_node(self, ref) -> Optional[NodeEntry]:
        """Resolve a node by NodeID or hex prefix (CLI convenience)."""
        node = self.nodes.get(ref)
        if node is not None:
            return node
        if isinstance(ref, str) and ref:
            matches = [n for nid, n in self.nodes.items()
                       if nid.hex().startswith(ref)]
            if len(matches) == 1:
                return matches[0]
        return None

    async def drain_node(self, p):
        """Drain a node (operator `rt drain <node>` or the autoscaler's
        if_idle reap): marks the controller's node row immediately and
        forwards the drain to the agent, which stops granting leases
        and redirects its queue.  ``node_id`` may be a NodeID or a hex
        prefix."""
        node = self._resolve_node(p.get("node_id"))
        if node is None:
            return {"ok": False, "error": "unknown node"}
        if_idle = p.get("if_idle", False)
        reason = p.get("reason") or (
            "idle timeout" if if_idle else "operator drain")
        grace_s = p.get("grace_s") or 0.0
        r = None
        cli = await self._agent(node.node_id)
        if cli is not None:
            try:
                r = await cli.call("drain", {
                    "if_idle": if_idle, "reason": reason,
                    "grace_s": grace_s or None,
                    "replace": p.get("replace", not if_idle)})
            except RpcError:
                r = None
        if r is None:
            # The agent never acknowledged: marking the row anyway
            # would split-brain — the agent keeps granting leases
            # while the controller excludes it, advertises phantom
            # replacement demand, and (drains being one-way) nothing
            # ever reconciles.  Fail the drain; the operator retries.
            return {"ok": False,
                    "error": "agent unreachable; node NOT drained"}
        if not r.get("ok"):
            return r  # agent refused (if_idle race) — stay undrained
        # Mark the row NOW — the agent's heartbeat confirms within a
        # period, but callers (doctor, the trainer's drain poll) must
        # see the state immediately.  The agent's own node_draining
        # callback usually beat us here (fired inside its drain
        # handler); the hooks run once either way.
        first = not node.draining
        node.draining = True
        node.drain_reason = reason
        remaining = r.get("remaining_s") or grace_s or \
            self.config.preemption_grace_s
        node.drain_deadline = time.time() + remaining
        node.drain_replace = p.get("replace", not if_idle)
        if first:
            await self._on_node_draining(node)
        return {"ok": True, "draining": True,
                "node_id": node.node_id.hex(),
                "deadline": node.drain_deadline}

    async def node_draining(self, p):
        """Agent-initiated drain notice (SIGTERM / preemption signal):
        mark the row and kick the migration hooks without waiting for
        the next heartbeat — the grace window can be seconds."""
        node = self.nodes.get(p["node_id"])
        if node is None:
            return {"ok": False}
        first = not node.draining
        node.draining = True
        node.drain_reason = p.get("reason", "")
        remaining = p.get("remaining_s")
        node.drain_deadline = (time.time() + float(remaining)
                               if remaining is not None
                               else p.get("deadline", 0.0))
        node.drain_replace = p.get("replace", True)
        if first:
            await self._on_node_draining(node)
        return {"ok": True}

    async def _on_node_draining(self, node: NodeEntry) -> None:
        logger.warning("node %s DRAINING (%s), deadline %s",
                       node.node_id.hex()[:8], node.drain_reason,
                       node.drain_deadline)
        self._publish("node", {"node_id": node.node_id,
                               "state": "DRAINING",
                               "reason": node.drain_reason,
                               "deadline": node.drain_deadline})
        # Placement groups with bundles on the node are marked for
        # migration (rescheduling happens on death — yanking bundles
        # out from under a live gang would kill the very training run
        # the drain window exists to checkpoint).
        if self._placement is not None:
            self._placement.on_node_draining(node.node_id)

    async def _health_loop(self) -> None:
        period = self.config.raylet_heartbeat_period_ms / 1000.0
        threshold = period * self.config.health_check_failure_threshold
        last_tick = time.time()
        while not self._shutdown.is_set():
            await asyncio.sleep(period)
            now = time.time()
            late = now - last_tick - period
            last_tick = now
            if late > period:
                # This loop itself ran late: the controller's event
                # loop was stalled, or the whole machine was (a TPU
                # runtime starting up pins memory, and a VM can stand
                # still for seconds while it does).  No heartbeat could
                # be received in that time either, so it is not counted
                # against the nodes.
                logger.warning("health loop ran %.1fs late; not counted "
                               "as missed heartbeats", late)
                for node in self.nodes.values():
                    node.last_heartbeat += late
            for node in list(self.nodes.values()):
                if node.alive and now - node.last_heartbeat > threshold:
                    await self._mark_node_dead(node, "missed heartbeats")

    async def _mark_node_dead(self, node: NodeEntry, reason: str) -> None:
        node.alive = False
        self._job_usage_by_node.pop(node.node_id.hex(), None)
        logger.warning("node %s dead: %s", node.node_id.hex()[:8], reason)
        self._publish("node", {"node_id": node.node_id, "state": "DEAD"})
        # Fail or restart every actor that lived there.
        for actor in list(self.actors.values()):
            if actor.node_id == node.node_id and actor.state in (ALIVE,
                                                                 PENDING):
                await self._handle_actor_failure(
                    actor, f"node {node.node_id.hex()[:8]} died")
        # Drop object locations on that node.  Entries that lose their
        # last copy are KEPT (with empty nodes) so borrower/owner state
        # survives lineage reconstruction; locate_object reports them as
        # location-less.  Fully-idle entries are dropped.
        gone = []
        for oid, info in self.object_dir.items():
            info["nodes"].discard(node.node_id)
            if not info["nodes"]:
                gone.append(oid)
        for oid in gone:
            self._publish("object_lost", {"object_id": oid})
            info = self.object_dir[oid]
            if not info["borrowers"] and not info.get("induced"):
                del self.object_dir[oid]
        if self._placement is not None:
            await self._placement.on_node_dead(node.node_id)

    # ---------------------------------------------------------------- actors
    async def register_actor(self, p):
        """Called by the owner before scheduling the creation task."""
        spec = p["spec"]
        entry = ActorEntry(
            actor_id=spec.actor_id, state=PENDING,
            class_name=p["class_name"], method_names=p["method_names"],
            name=spec.actor_name, namespace=spec.namespace,
            restarts_remaining=spec.max_restarts,
            creation_spec=spec, owner_addr=p.get("owner_addr", ""),
            detached=p.get("detached", False),
            max_concurrency=spec.max_concurrency)
        key = (spec.namespace, spec.actor_name)
        if spec.actor_name:
            if key in self.named_actors:
                return {"ok": False,
                        "error": f"actor name {spec.actor_name!r} taken"}
            self.named_actors[key] = spec.actor_id
        self.actors[spec.actor_id] = entry
        self._mark_dirty()
        return {"ok": True}

    async def register_actors(self, p):
        """Bulk actor registration (owner-side 5 ms coalescing window):
        a 100-actor fan-out costs a handful of controller round trips
        instead of one per actor.  Per-item results keep the single-
        registration semantics (incl. name-conflict refusal)."""
        return {"results": [await self.register_actor(item)
                            for item in p.get("items") or []]}

    async def actors_started(self, p):
        """Bulk actor-started hellos (agent-side coalescing relay) —
        the fan-in half of the fast path register_actors opens."""
        return {"results": [await self.actor_started(item)
                            for item in p.get("items") or []]}

    async def actor_started(self, p):
        actor = self.actors.get(p["actor_id"])
        if actor is None:
            return {"ok": False}
        if actor.state == DEAD:
            # Killed while still starting; tell the worker to exit.
            return {"ok": False, "kill": True}
        if actor.state == ALIVE and actor.worker_addr and \
                actor.worker_addr != p["worker_addr"]:
            # First registration wins (ref: gcs_actor_manager single-
            # instance invariant): a duplicate creation attempt — the
            # owner retried after a transient connection loss while
            # the first attempt's __init__ was still running — must
            # exit instead of clobbering the live instance's address.
            return {"ok": False, "kill": True}
        actor.state = ALIVE
        actor.node_id = p["node_id"]
        actor.worker_addr = p["worker_addr"]
        self._publish("actor", {"actor_id": actor.actor_id, "state": ALIVE,
                                "worker_addr": actor.worker_addr})
        return {"ok": True}

    async def actor_died(self, p):
        """Agent-reported worker exit for an actor (crash or kill)."""
        actor = self.actors.get(p["actor_id"])
        if actor is None:
            return {"ok": False}
        if p.get("creation_failed"):
            actor.restarts_remaining = 0
        await self._handle_actor_failure(
            actor, p.get("reason", "worker exited"),
            no_restart=p.get("no_restart", False))
        return {"ok": True}

    async def _handle_actor_failure(self, actor: ActorEntry, reason: str,
                                    no_restart: bool = False) -> None:
        if actor.state == DEAD:
            return
        if not no_restart and actor.restarts_remaining != 0:
            if actor.restarts_remaining > 0:
                actor.restarts_remaining -= 1
            actor.state = RESTARTING
            actor.worker_addr = ""
            self._publish("actor", {"actor_id": actor.actor_id,
                                    "state": RESTARTING})
            spawn_task(self._restart_actor(actor))
        else:
            actor.state = DEAD
            actor.death_reason = reason
            actor.worker_addr = ""
            if actor.name:
                self.named_actors.pop((actor.namespace, actor.name), None)
            self._publish("actor", {"actor_id": actor.actor_id,
                                    "state": DEAD, "reason": reason})

    async def _restart_actor(self, actor: ActorEntry) -> None:
        """Re-run the creation spec on a live node (ref:
        gcs_actor_manager.h:553 restart flow)."""
        delay = self.config.task_retry_delay_ms / 1000.0
        for _attempt in range(60):
            await asyncio.sleep(delay)
            for node in self.nodes.values():
                if not node.alive:
                    continue
                cli = await self._agent(node.node_id)
                if cli is None:
                    continue
                try:
                    r = await cli.call("restart_actor",
                                       {"spec": actor.creation_spec})
                    if r.get("ok"):
                        return  # agent will report actor_started
                except RpcError:
                    continue
            delay = min(delay * 2, 2.0)
        await self._handle_actor_failure(actor, "restart failed",
                                         no_restart=True)

    async def get_actor(self, p):
        actor = self.actors.get(p["actor_id"])
        if actor is None:
            return None
        spec = actor.creation_spec
        return {"actor_id": actor.actor_id, "state": actor.state,
                "worker_addr": actor.worker_addr,
                "class_name": actor.class_name,
                "method_names": actor.method_names,
                "death_reason": actor.death_reason,
                "max_concurrency": actor.max_concurrency,
                # Name-lookup handles must keep concurrency-group
                # routing (a reconstructed handle falling back to the
                # ordered submit path would reintroduce head-of-line
                # blocking across groups).
                "concurrency_groups":
                    dict(getattr(spec, "concurrency_groups", {}) or {})
                    if spec is not None else {},
                "method_options":
                    dict(getattr(spec, "method_options", {}) or {})
                    if spec is not None else {}}

    async def list_actors(self, _p):
        return [
            {"actor_id": a.actor_id, "state": a.state,
             "class_name": a.class_name, "name": a.name,
             "node_id": a.node_id, "worker_addr": a.worker_addr}
            for a in self.actors.values()
        ]

    async def lookup_named_actor(self, p):
        aid = self.named_actors.get((p.get("namespace", ""), p["name"]))
        if aid is None:
            return None
        return await self.get_actor({"actor_id": aid})

    async def kill_actor(self, p):
        actor = self.actors.get(p["actor_id"])
        if actor is None:
            return {"ok": False}
        actor.restarts_remaining = 0 if p.get("no_restart", True) else \
            actor.restarts_remaining
        if actor.node_id is not None:
            cli = await self._agent(actor.node_id)
            if cli is not None:
                aid = actor.actor_id

                async def _kill():
                    try:
                        await cli.call("kill_worker", {"actor_id": aid})
                    except RpcError:
                        pass

                if p.get("no_restart", True):
                    # Off the reply path: a fleet teardown issues
                    # hundreds of kills, and each agent round trip
                    # serialized into the caller's kill() call
                    # dominates teardown time.  Safe only because the
                    # actor id is terminal here — nothing rebinds it.
                    # The SIGKILL itself is asynchronous either way
                    # (death is observed by the agent's reap loop).
                    spawn_task(_kill())
                else:
                    # Restartable: the kill MUST land before the
                    # restart path can bind a fresh worker to the same
                    # actor id, or the late SIGKILL (resolved by
                    # actor_id agent-side) takes down the new
                    # incarnation.
                    await _kill()
        await self._handle_actor_failure(actor, "killed via kill()",
                                         no_restart=p.get("no_restart", True))
        return {"ok": True}

    async def worker_exited(self, p):
        """Generic notification; actor workers route through actor_died."""
        return {"ok": True}

    # -------------------------------------------------------------------- kv
    async def kv_put(self, p):
        overwrite = p.get("overwrite", True)
        if not overwrite and p["key"] in self.kv:
            return {"ok": False, "exists": True}
        self.kv[p["key"]] = p["value"]
        self.kv_list_counts.pop(p["key"], None)  # no longer a list value
        if p["key"].startswith("runtime_env/pkg/"):
            self._touch_pkg(p["key"], len(p["value"]))
        self._publish("kv", {"key": p["key"]})
        return {"ok": True}

    def _touch_pkg(self, key: str, size: int) -> None:
        """LRU cap on runtime-env package blobs: the KV is controller
        memory, and every edited working_dir is a new content digest —
        without eviction a long-lived cluster grows without bound (ref:
        runtime_env URI reference counting / cache GC in
        _private/runtime_env/packaging.py)."""
        from collections import OrderedDict

        lru = getattr(self, "_pkg_lru", None)
        if lru is None:
            lru = self._pkg_lru = OrderedDict()
        lru.pop(key, None)
        lru[key] = size
        cap = self.config.runtime_env_cache_bytes
        while sum(lru.values()) > cap and len(lru) > 1:
            victim, _ = lru.popitem(last=False)
            self.kv.pop(victim, None)
            logger.info("evicted runtime_env package %s (cache > %d)",
                        victim, cap)

    async def kv_get(self, p):
        val = self.kv.get(p["key"])
        if val is not None and p["key"].startswith("runtime_env/pkg/"):
            self._touch_pkg(p["key"], len(val))
        return val

    async def kv_del(self, p):
        self.kv.pop(p["key"], None)
        self.kv_list_counts.pop(p["key"], None)
        self._mark_dirty()
        return {"ok": True}

    async def kv_keys(self, p):
        prefix = p.get("prefix", "")
        return [k for k in self.kv if k.startswith(prefix)]

    async def kv_append(self, p):
        """Atomic append to a list value — rendezvous building block.
        Items are stored length-prefixed so binary values (including NUL
        bytes) round-trip intact; read back with kv_list."""
        key = p["key"]
        cur = self.kv.get(key, b"")
        item = p["value"]
        self.kv[key] = cur + len(item).to_bytes(4, "little") + item
        if key not in self.kv_list_counts:  # key may predate via kv_put
            self.kv_list_counts[key] = len(self._kv_items(key)) - 1
        self.kv_list_counts[key] += 1
        self._publish("kv", {"key": key})
        return {"count": self.kv_list_counts[key]}

    def _kv_items(self, key: str) -> List[bytes]:
        blob = self.kv.get(key, b"")
        items, pos = [], 0
        while pos + 4 <= len(blob):
            n = int.from_bytes(blob[pos:pos + 4], "little")
            pos += 4
            items.append(blob[pos:pos + n])
            pos += n
        return items

    async def kv_list(self, p):
        """Decode a kv_append-built list value into its items."""
        return self._kv_items(p["key"])

    # -------------------------------------------------------- object plane
    def _add_location(self, node_id, oid, size) -> None:
        info = self._dir_entry(oid)  # merges with placeholder borrows
        info["nodes"].add(node_id)
        info["size"] = size

    def _remove_location(self, node_id, oid) -> None:
        info = self.object_dir.get(oid)
        if info is not None:
            info["nodes"].discard(node_id)
            if not info["nodes"]:
                self._drop_if_idle(oid)  # keep borrower/owner state

    async def publish_locations(self, p):
        for oid, size in p["objects"]:
            self._add_location(p["node_id"], oid, size)
        return {"ok": True}

    async def remove_locations(self, p):
        for oid in p["objects"]:
            self._remove_location(p["node_id"], oid)
        return {"ok": True}

    async def update_locations(self, p):
        """Coalesced, ORDERED add/remove location updates from one
        node's agent (the object plane's hot-path publication traffic,
        batched agent-side so a burst of put/release cycles costs one
        frame instead of one call round trip each)."""
        node_id = p["node_id"]
        for kind, item in p["updates"]:
            if kind == "add":
                self._add_location(node_id, item[0], item[1])
            else:
                self._remove_location(node_id, item)
        return {"ok": True}

    async def locate_objects(self, p):
        """Bulk existence probe (wait() fast path): one RPC answers
        readiness for a whole ref list instead of two per ref."""
        out = {}
        for oid in p["object_ids"]:
            info = self.object_dir.get(oid)
            out[oid] = bool(info and info["nodes"])
        return out

    async def locate_object(self, p):
        info = self.object_dir.get(p["object_id"])
        if info is None or not info["nodes"]:
            return None
        nodes = []
        for nid in info["nodes"]:
            node = self.nodes.get(nid)
            if node is not None and node.alive:
                nodes.append({"node_id": nid, "agent_addr": node.agent_addr})
        return {"nodes": nodes, "size": info["size"]}

    async def free_object(self, p):
        oid = p["object_id"]
        info = self.object_dir.pop(oid, None)
        if info is None:
            return {"ok": True}
        for nid in list(info["nodes"]):
            cli = await self._agent(nid)
            if cli is not None:
                try:
                    await cli.notify("delete_object", {"object_id": oid})
                except RpcError:
                    pass
        # Cascade: borrows induced by refs embedded in this object's
        # payload end with the container (the embedded refs can only be
        # materialized out of a payload that no longer exists).
        for emb in info.get("induced", ()):
            await self.remove_borrower({
                "object_id": emb, "holder": f"obj:{oid.hex()}"})
        return {"ok": True}

    # --------------------------------------- distributed reference counting
    # (ref: src/ray/core_worker/reference_count.h:66 — redesigned around
    # this controller's centralized object directory: each process reports
    # only its 0<->1 holder transitions, the controller frees when the
    # owner has released AND no borrowers remain.)
    async def owner_release(self, p):
        """The owning process dropped its last reference."""
        oid = p["object_id"]
        info = self.object_dir.get(oid)
        if info is None:
            return {"ok": True}  # never materialized or already freed
        info["owner_released"] = True
        if not info["borrowers"]:
            await self.free_object({"object_id": oid})
        return {"ok": True}

    def _dir_entry(self, oid: ObjectID) -> Dict:
        """Get-or-create a directory entry.  Borrows may legitimately
        arrive before the object is published (a ref travels in a task
        spec while the producer is still sealing); the placeholder keeps
        the borrow so the eventual publish + owner release can't free the
        object out from under the borrower."""
        info = self.object_dir.get(oid)
        if info is None:
            info = self.object_dir[oid] = {
                "nodes": set(), "size": 0,
                "borrowers": set(), "owner_released": False}
        return info

    def _drop_if_idle(self, oid: ObjectID) -> None:
        info = self.object_dir.get(oid)
        if info is not None and not info["nodes"] \
                and not info["borrowers"] and not info.get("induced"):
            del self.object_dir[oid]

    async def add_borrower(self, p):
        self._dir_entry(p["object_id"])["borrowers"].add(p["holder"])
        return {"ok": True}

    async def remove_borrower(self, p):
        oid = p["object_id"]
        info = self.object_dir.get(oid)
        if info is None:
            return {"ok": True}
        info["borrowers"].discard(p["holder"])
        if info["owner_released"] and not info["borrowers"]:
            await self.free_object({"object_id": oid})
        else:
            self._drop_if_idle(oid)
        return {"ok": True}

    async def link_induced_borrows(self, p):
        """Register borrows held on behalf of refs embedded inside a
        container object's serialized payload; they are released when the
        container is freed (free_object cascade)."""
        container = p["container"]
        holder = f"obj:{container.hex()}"
        for emb in p["embedded"]:
            self._dir_entry(emb)["borrowers"].add(holder)
        cinfo = self._dir_entry(container)
        cinfo.setdefault("induced", set()).update(p["embedded"])
        return {"ok": True}

    # ---------------------------------------------------------------- pubsub
    async def poll_events(self, p):
        """Cursor-based long-poll (ref: src/ray/pubsub long-poll design).
        If the cursor predates trimmed history on any requested channel,
        the reply carries cursor_expired=True: events were lost and the
        subscriber must do a full resync (list_actors/list_nodes)."""
        cursor = p.get("cursor", 0)
        channels = p.get("channels", ["actor", "node"])
        timeout = p.get("timeout", 30.0)
        deadline = asyncio.get_event_loop().time() + timeout
        while True:
            # Recomputed each pass: a trim can happen while we long-poll.
            expired = any(cursor < self.events_trimmed_to.get(ch, 0)
                          for ch in channels)
            out = []
            for ch in channels:
                for seq, data in self.events.get(ch, []):
                    if seq > cursor:
                        out.append((seq, ch, data))
            if out or expired:
                out.sort()
                new_cursor = out[-1][0] if out else \
                    max(cursor, self.event_seq)
                return {"events": out, "cursor": new_cursor,
                        "cursor_expired": expired}
            remaining = deadline - asyncio.get_event_loop().time()
            if remaining <= 0:
                return {"events": [], "cursor": cursor}
            ev = asyncio.Event()
            self.event_waiters.append(ev)
            try:
                await asyncio.wait_for(ev.wait(), remaining)
            except asyncio.TimeoutError:
                pass
            finally:
                self.event_waiters.remove(ev)

    # ------------------------------------------------------------------ jobs
    # ----------------------------------------------------- task events
    async def worker_logs(self, p):
        """Batched worker log lines from node-agent tailers; fanned to
        drivers over the worker_logs pubsub channel (ref:
        log_monitor.py lines -> GCS pubsub -> driver print)."""
        for rec in p.get("batch", []):
            self._publish("worker_logs", rec)
        return {"ok": True}

    async def task_events(self, p):
        """Batched task state transitions from workers (ref:
        task_event_buffer.h:222 flush -> gcs_task_manager.h:86)."""
        cap = max(self.config.task_event_buffer_size, 16)
        recv_ts = time.time()
        # Owner-side explainability events trimmed before they could
        # flush count as drops too — a gapped `rt explain` chain must
        # be attributable to backpressure, not read as a phantom bug.
        self.task_events_dropped += int(p.get("dropped") or 0)
        hp = p.get("hotpath")
        if hp:
            # Sampled phase-stamp records piggybacked on the owner's
            # event flush — aggregated here, read by `rt hotpath`.
            self.hotpath_sink.add(p.get("source") or "", hp)
        for ev in p["events"]:
            tid = ev["task_id"]
            rec = self.task_records.get(tid)
            if rec is None:
                if len(self.task_records) >= cap:
                    # Evict the oldest finished record first.
                    for k, r in self.task_records.items():
                        if r.get("state") in ("FINISHED", "FAILED"):
                            del self.task_records[k]
                            break
                    else:
                        self.task_records.popitem(last=False)
                    self.task_events_dropped += 1
                rec = self.task_records[tid] = {
                    "task_id": tid, "times": {}}
            rec.update({k: v for k, v in ev.items()
                        if k not in ("task_id", "state", "ts",
                                     "detail", "attempt")})
            state = ev.get("state")
            if state:
                # Owner-side scheduling events (QUEUED/PIPELINED/...)
                # and worker-side execution events flush on different
                # cadences AND carry timestamps from different hosts,
                # so neither arrival order nor raw timestamps resolve
                # the headline state.  Rank by execution attempt
                # first (a retry's events supersede the previous
                # attempt's terminal state), then lifecycle tier
                # (terminal > running > scheduling); timestamps only
                # break ties within the same attempt and tier.
                cur = rec.get("state")
                cur_att = int(rec.get("attempt") or 0)
                new_att = int(ev.get("attempt") or 0)
                cur_tier = _STATE_TIER.get(cur, 1)
                new_tier = _STATE_TIER.get(state, 1)
                if cur is None or new_att > cur_att or (
                        new_att == cur_att
                        and (new_tier > cur_tier
                             or (new_tier == cur_tier
                                 and ev["ts"] >= rec["times"].get(
                                     cur, float("-inf"))))):
                    rec["state"] = state
                    rec["attempt"] = max(cur_att, new_att)
                if new_att >= cur_att:
                    # A late batch from a PREVIOUS attempt must not
                    # roll timestamps back under the current one.
                    rec["times"][state] = ev["ts"]
                    # Receipt-clock shadow: reporter timestamps come
                    # from arbitrary host clocks, so age computations
                    # (the stuck-task detector) use the controller's
                    # receipt time; durations still use the
                    # reporter-clock times (same-host deltas).
                    rec.setdefault("times_recv", {})[state] = recv_ts
                # Full transition chain with reason tags (scheduler
                # explainability: queued -> lease_requested ->
                # pipelined/granted -> running -> finished/requeued),
                # bounded per task so a retry storm can't grow a
                # record without limit.
                chain = rec.setdefault("transitions", [])
                detail = dict(ev.get("detail") or {})
                if new_att:
                    detail["attempt"] = new_att
                chain.append([ev["ts"], state, detail])
                if len(chain) > 64:
                    del chain[:len(chain) - 64]
        self._mark_dirty()
        return {"ok": True}

    async def list_tasks(self, p):
        out = []
        limit = p.get("limit", 1000)
        flt_state = p.get("state")
        flt_name = p.get("name")
        for rec in reversed(self.task_records.values()):
            if flt_state and rec.get("state") != flt_state:
                continue
            if flt_name and rec.get("name") != flt_name:
                continue
            out.append(rec)
            if len(out) >= limit:
                break
        return {"tasks": out, "dropped": self.task_events_dropped,
                "total": len(self.task_records)}

    async def get_task(self, p):
        return self.task_records.get(p["task_id"])

    async def explain_task(self, p):
        """Scheduler explainability: the full transition chain of one
        task (`rt explain <task_id>`; prefix match accepted).  Answers
        *why* a task sat where it did — which lease it pipelined onto,
        which agent queued its lease request, whether it was requeued
        off a blocked worker — without reading agent logs."""
        tid = p.get("task_id") or ""
        rec = self.task_records.get(tid)
        if rec is None and tid:
            matches = [r for t, r in self.task_records.items()
                       if t.startswith(tid)]
            if len(matches) == 1:
                rec = matches[0]
            elif len(matches) > 1:
                return {"ok": False,
                        "error": f"task id prefix {tid!r} is ambiguous "
                                 f"({len(matches)} matches)"}
        if rec is None:
            return {"ok": False, "error": f"no task record {tid!r} "
                                          f"(dropped or never seen)"}
        return {"ok": True, "task": rec}

    # ------------------------------------------------- health plane
    async def collective_entries(self, p):
        """Per-source inflight collective stamps (gang watchdog).
        Replace semantics: each report is the source's CURRENT set."""
        src = p.get("source") or "?"
        now = time.time()
        # Rebase entry times onto the CONTROLLER clock from the
        # reporter's age delta: worker-host wall clocks can be
        # arbitrarily skewed, and the watchdog deadline is small
        # enough that skew alone would forge (or mask) a hang.
        entries = []
        for e in p.get("entries") or []:
            if "age_s" in e:
                e = {**e, "since": now - float(e["age_s"])}
            entries.append(e)
        self.collective_reports[src] = {"ts": now, "entries": entries}
        # Prune dead reporters here too, not just in the doctor-feed
        # merge: under worker churn on a cluster nobody runs `rt
        # doctor` against, the per-source dict would otherwise grow
        # one entry per dead worker forever.
        self._prune_collective_reports(now)
        return {"ok": True}

    def _collective_horizon(self) -> float:
        return max(self.config.metrics_report_period_s * 3, 5.0)

    def _prune_collective_reports(self, now: float) -> None:
        horizon = self._collective_horizon()
        for src in [s for s, v in list(self.collective_reports.items())
                    if now - v["ts"] > horizon * 4]:
            del self.collective_reports[src]  # dead reporter

    def _merged_collective_inflight(self, now: float) -> List[Dict]:
        """Merge fresh per-source stamps into one row per (group,
        seq): which ranks are inside, since when, expecting how many."""
        horizon = self._collective_horizon()
        merged: Dict[Tuple[str, int], Dict] = {}
        self._prune_collective_reports(now)
        for src, rep in self.collective_reports.items():
            if now - rep["ts"] > horizon:
                continue  # stale: the process stopped refreshing
            for e in rep["entries"]:
                key = (e.get("group", "?"), int(e.get("seq", 0)))
                rec = merged.get(key)
                if rec is None:
                    rec = merged[key] = {
                        "group": key[0], "seq": key[1],
                        "op": e.get("op", "?"),
                        "backend": e.get("backend", "?"),
                        "world": int(e.get("world", 0)),
                        "ranks": {}}
                rec["ranks"][int(e.get("rank", -1))] = \
                    float(e.get("since", now))
        return list(merged.values())

    async def report_autoscaler_decision(self, p):
        self.autoscaler_decisions.append({
            "ts": p.get("ts") or time.time(),
            "demands": p.get("demands", 0),
            "launched": list(p.get("launched") or []),
            "terminated": list(p.get("terminated") or []),
            "preempted": list(p.get("preempted") or []),
            "unsatisfied": list(p.get("unsatisfied") or [])})
        return {"ok": True}

    async def doctor_feed(self, _p):
        """One-stop raw feed for `rt doctor` / /api/doctor: the
        health-plane state only the controller holds.  The client
        (util/doctor.py) combines it with the regular state RPCs."""
        now = time.time()
        return {
            "ts": now,
            "collective_inflight": self._merged_collective_inflight(
                now),
            "autoscaler_decisions": list(self.autoscaler_decisions),
            "flight": list(self.flight_dumps.values()),
            "task_events_dropped": self.task_events_dropped,
        }

    async def list_objects(self, p):
        out = []
        limit = p.get("limit", 1000)
        for oid, info in self.object_dir.items():
            out.append({
                "object_id": oid.hex() if hasattr(oid, "hex") else str(oid),
                "size": info.get("size", 0),
                "nodes": [n.hex() if hasattr(n, "hex") else str(n)
                          for n in info.get("nodes", ())],
            })
            if len(out) >= limit:
                break
        return {"objects": out, "total": len(self.object_dir)}

    async def list_jobs(self, p):
        return {"jobs": [dict(j, job_id=jid)
                         for jid, j in self.jobs.items()]}

    # ------------------------------------------------- multi-tenant jobs
    async def job_register(self, p):
        """Register a submitted job's multi-tenant metadata (priority,
        optional quota) — called by the job supervisor before the
        entrypoint spawns, so admission/quota decisions never race the
        job's first lease request."""
        job_id = p["job_id"]
        quota = p.get("quota") or None
        if quota is not None:
            quota = {str(k): float(v) for k, v in quota.items()}
        self.job_plane[job_id] = {
            "job_id": job_id,
            "priority": int(p.get("priority") or 0),
            "quota": quota,
            "entrypoint": p.get("entrypoint", ""),
            "submitted": p.get("ts") or time.time(),
        }
        self._publish("job", {"job_id": job_id, "state": "REGISTERED",
                              "priority": self.job_plane[job_id]
                              ["priority"]})
        return {"ok": True}

    def _tenant_of_hex(self, job_hex: str) -> str:
        """Map an internal driver job hex to its tenant job id."""
        cache = getattr(self, "_tenant_cache", None)
        if cache is None:
            cache = self._tenant_cache = {}
        hit = cache.get(job_hex)
        if hit is not None:
            return hit
        for jid, rec in self.jobs.items():
            h = JobID.from_int(jid).hex()
            cache[h] = rec.get("tenant", "")
        return cache.get(job_hex, "")

    def _job_usage(self, job_id: str,
                   exclude_pg=None) -> Dict[str, float]:
        """Cluster-wide resource usage attributed to one tenant job:
        committed placement-group bundles (controller's own books) +
        agent-reported plain leases (heartbeat overlay)."""
        used: Dict[str, float] = {}
        if self._placement is not None:
            for entry in self._placement._groups.values():
                if getattr(entry, "job", "") != job_id or \
                        entry.state != "CREATED" or \
                        entry.pg_id == exclude_pg:
                    continue
                for b in entry.bundles:
                    for k, v in b.items():
                        used[k] = used.get(k, 0.0) + v
        for per_job in self._job_usage_by_node.values():
            for job_hex, res in per_job.items():
                if self._tenant_of_hex(job_hex) != job_id:
                    continue
                for k, v in res.items():
                    used[k] = used.get(k, 0.0) + v
        return used

    def _job_is_terminal(self, job_id: str) -> bool:
        import json as _json

        raw = self.kv.get(f"job/{job_id}/status")
        if not raw:
            return False
        try:
            return _json.loads(raw).get("status") in (
                "SUCCEEDED", "FAILED", "STOPPED")
        except (ValueError, TypeError):
            return False

    def _job_quota_view(self) -> Dict[str, Dict]:
        """The per-internal-job view shipped to agents in heartbeat
        replies: only jobs whose tenant registered a quota or a
        non-zero priority (keeps the common single-tenant heartbeat
        payload empty).  Terminal tenants and dead drivers are
        skipped — they can request nothing, and without the filter
        the view (computed per heartbeat, shipped to every agent)
        would grow with job history forever."""
        if not self.job_plane:
            return {}
        interesting = {j: rec for j, rec in self.job_plane.items()
                       if (rec.get("quota") or rec.get("priority"))
                       and not self._job_is_terminal(j)}
        if not interesting:
            return {}
        out: Dict[str, Dict] = {}
        usage_cache: Dict[str, Dict[str, float]] = {}
        for jid, rec in self.jobs.items():
            if not rec.get("alive", True):
                continue  # a dead driver can't request leases
            tenant = rec.get("tenant", "")
            plane = interesting.get(tenant)
            if plane is None:
                continue
            if tenant not in usage_cache:
                usage_cache[tenant] = self._job_usage(tenant)
            out[JobID.from_int(jid).hex()] = {
                "job": tenant,
                "priority": plane["priority"],
                "quota": plane.get("quota"),
                "used": usage_cache[tenant],
            }
        return out

    async def jobs_overview(self, p):
        """`rt jobs` / /api/jobs: every submitted job with priority,
        quota, live resource usage, state, and submission time.
        ``job_id`` prefix-filters (the `rt explain` convention)."""
        prefix = (p or {}).get("job_id") or ""
        import json as _json

        ids = set(self.job_plane)
        for key in self.kv:
            if key.startswith("job/") and key.endswith("/status"):
                ids.add(key.split("/", 2)[1])
        rows = []
        for job_id in sorted(ids):
            if prefix and not job_id.startswith(prefix):
                continue
            plane = self.job_plane.get(job_id, {})
            status: Dict[str, Any] = {}
            raw = self.kv.get(f"job/{job_id}/status")
            if raw:
                try:
                    status = _json.loads(raw)
                except (ValueError, TypeError):
                    status = {}
            row = {
                "job_id": job_id,
                "priority": plane.get("priority", 0),
                "quota": plane.get("quota"),
                "usage": self._job_usage(job_id),
                "state": status.get("status", "?"),
                "message": status.get("message", ""),
                "entrypoint": status.get("entrypoint")
                or plane.get("entrypoint", ""),
                "submitted": plane.get("submitted")
                or status.get("ts", 0.0),
            }
            pre = self.preempting.get(job_id)
            if pre is not None:
                row["preempting"] = {
                    "reason": pre.get("reason", ""),
                    "by": pre.get("by", ""),
                    "remaining_s": max(pre["deadline"] - time.time(),
                                       0.0)}
            rows.append(row)
        return {"jobs": rows}

    async def preempt_job(self, p):
        """Mark a job for preemption: the victim's trainer observes it
        on its drain-poll cadence (checkpoint-on-notice inside the
        grace window); at the deadline the enforcement loop evicts the
        job's placement groups, so the gang dies as an ANNOUNCED
        failure and restarts from the notice checkpoint."""
        job_id = p["job_id"]
        if job_id in self.preempting:
            return {"ok": True, "already": True,
                    "deadline": self.preempting[job_id]["deadline"]}
        grace = p.get("grace_s")
        if grace is None:  # explicit 0 means evict immediately
            grace = self.config.preemption_grace_s
        rec = {"job_id": job_id, "reason": p.get("reason", "preempted"),
               "by": p.get("by", ""), "ts": time.time(),
               "deadline": time.time() + max(float(grace), 0.0)}
        self.preempting[job_id] = rec
        logger.warning("job %s preempting (%s): grace %.1fs",
                       job_id, rec["reason"], grace)
        self._publish("job", {"job_id": job_id, "state": "PREEMPTING",
                              "reason": rec["reason"],
                              "deadline": rec["deadline"]})
        return {"ok": True, "deadline": rec["deadline"]}

    async def job_preemption_state(self, p):
        """Polled by the victim's trainer driver (its drain-poll
        cadence): the deadline crosses hosts as REMAINING seconds, the
        same clock discipline as node drains."""
        rec = self.preempting.get(p.get("job_id") or "")
        if rec is None:
            return {"preempting": False}
        return {"preempting": True,
                "reason": rec.get("reason", ""),
                "by": rec.get("by", ""),
                "remaining_s": max(rec["deadline"] - time.time(), 0.0)}

    async def _job_preemption_loop(self) -> None:
        """Enforce preemption deadlines: once the grace expires, evict
        the victim's placement groups (killing the gang workers), so
        capacity frees for the admission loop's next pass.  The notice
        is cleared BEFORE enforcement — the victim's next attempt must
        not see a stale interrupt and checkpoint-on-notice forever."""
        while not self._shutdown.is_set():
            await asyncio.sleep(0.25)
            now = time.time()
            for job_id, rec in list(self.preempting.items()):
                if now < rec["deadline"]:
                    continue
                del self.preempting[job_id]
                self._tenant_cache = {}
                logger.warning("job %s preemption grace expired; "
                               "evicting its gangs", job_id)
                self._publish("job", {"job_id": job_id,
                                      "state": "PREEMPTED",
                                      "reason": rec.get("reason", "")})
                self.autoscaler_decisions.append({
                    "ts": now, "demands": 0, "launched": [],
                    "terminated": [], "unsatisfied": [],
                    "preempted": [f"job:{job_id}"]})
                if self._placement is not None:
                    try:
                        await self._placement.preempt_job_groups(
                            job_id, reason=rec.get("reason", ""))
                    except Exception:
                        logger.exception("preemption enforcement for "
                                         "job %s failed", job_id)

    # --------------------------------------------------------- metrics
    async def report_metrics(self, p):
        now = time.time()
        self.metrics_sources[p["source"]] = {
            "snapshot": p["snapshot"], "ts": now}
        # Bounded per-source history for dashboard time series (ref:
        # dashboard/modules/reporter/ — utilization over time, not
        # just the current snapshot).  ~30 min at the default 5 s
        # report period; never persisted.
        from collections import deque

        hist = getattr(self, "_metrics_history", None)
        if hist is None:
            hist = self._metrics_history = {}
        flat: Dict[str, float] = {}
        for metric in p["snapshot"]:
            for s in metric.get("series", []):
                tags = s.get("tags") or {}
                key = metric["name"]
                if tags:
                    key += "{" + ",".join(
                        f"{k}={v}" for k, v in sorted(tags.items())) \
                        + "}"
                if "value" in s:
                    flat[key] = float(s["value"])
                elif "hist" in s:
                    # Histogram series flatten to their running count
                    # and sum — enough for rate/mean time series.
                    flat[key + "_count"] = float(s["hist"]["count"])
                    flat[key + "_sum"] = float(s["hist"]["sum"])
        dq = hist.get(p["source"])
        if dq is None:
            dq = hist[p["source"]] = deque(maxlen=360)
        dq.append((now, flat))
        return {"ok": True}

    async def report_flight_dump(self, p):
        """A node agent forwards a dead worker's flight-recorder dump
        (ref: the reference's dashboard event aggregation; here the
        postmortem ring of a reaped process)."""
        src = p.get("source") or "?"
        self.flight_dumps[src] = {
            "source": src, "reason": p.get("reason", ""),
            # Receipt-clock shadow (same discipline as task times):
            # the dump's own ts is the DYING WORKER's wall clock, not
            # comparable with the controller clock ages are computed
            # against.
            "ts": p.get("ts"), "ts_recv": time.time(),
            "path": p.get("path", ""),
            "sticky": p.get("sticky") or {},
            "events": (p.get("events") or [])[-200:]}
        self.flight_dumps.move_to_end(src)
        while len(self.flight_dumps) > 32:
            self.flight_dumps.popitem(last=False)
        return {"ok": True}

    async def report_spans(self, p):
        """Span records drained from a process's ring (relayed by its
        node agent, or pushed directly by the driver).  The sink is one
        bounded deque — oldest spans fall off first, same policy as the
        task-event sink."""
        src = p.get("source") or "?"
        node = p.get("node_id")
        for s in p.get("spans") or []:
            s.setdefault("source", src)
            if node and not s.get("node_id"):
                s["node_id"] = node
            self.span_records.append(s)
            self.spans_received += 1
            # Finished ingress spans feed the slow-request exemplar
            # ring (request id + duration + deployment + dominant-
            # phase inputs live in the sink for assembly on demand).
            if s.get("name") == "ingress":
                tags = s.get("tags") or {}
                rid = tags.get("request_id")
                if rid:
                    try:
                        self.request_exemplar_ring.offer(
                            rid,
                            max(float(s.get("end", 0.0))
                                - float(s.get("start", 0.0)), 0.0),
                            deployment=tags.get("deployment", "?"),
                            ts=time.time(),
                            outcome=tags.get("outcome", "?"),
                            status_class=tags.get("status_class", "?"))
                    except Exception:
                        pass  # observability must never fail the relay
        return {"ok": True}

    async def request_exemplars(self, p):
        """Slowest-request exemplars in the current window (slowest
        first) — the `rt trace` listing and find_slow_requests feed."""
        return {"exemplars": self.request_exemplar_ring.snapshot(),
                "window_s": self.request_exemplar_ring.window_s}

    async def list_spans(self, p):
        limit = (p or {}).get("limit", 10000)
        cat = (p or {}).get("cat")
        out = []
        for s in reversed(self.span_records):
            if cat and s.get("cat") != cat:
                continue
            out.append(s)
            if len(out) >= limit:
                break
        out.reverse()  # chronological-ish (ring append order)
        return {"spans": out, "total": len(self.span_records),
                "received": self.spans_received}

    async def report_profile(self, p):
        """A node agent reports a finished on-demand profiler capture
        (artifact stays on the node's disk; this records where)."""
        self.profile_artifacts.append({
            "source": p.get("source", "?"), "kind": p.get("kind", "jax"),
            "path": p.get("path", ""), "node_id": p.get("node_id"),
            "ts": p.get("ts") or time.time()})
        return {"ok": True}

    def _prune_metrics_sources(self, now: float) -> None:
        """Drop sources that stopped reporting (dead workers/nodes) —
        a gauge from a dead process must not render as current, and
        the map must not grow with worker churn."""
        horizon = max(self.config.metrics_report_period_s * 6, 30.0)
        for src in [s for s, v in self.metrics_sources.items()
                    if now - v["ts"] > horizon]:
            del self.metrics_sources[src]

    async def hotpath(self, p):
        """Cluster-wide hot-path phase decomposition: aggregated
        sampled task stamp records (`rt hotpath`, /api/hotpath)."""
        return self.hotpath_sink.snapshot()

    def _self_metric_snaps(self):
        """Controller-process introspection rendered in registry
        snapshot shape: its own event-loop lag, RPC handler stats and
        the cluster-wide task-event drop counter — so the controller
        shows up in telemetry/doctor like any other reporting source."""
        snaps = [
            {"name": "rt_task_events_dropped_total", "kind": "counter",
             "description": "Task lifecycle events dropped cluster-wide"
                            " (owner-side trims + controller evictions).",
             "series": [{"tags": {},
                         "value": float(self.task_events_dropped)}]},
        ]
        lag = getattr(self, "_loop_lag", None)
        if lag is not None:
            snaps.extend(lag.metric_snaps())
        snaps.extend(self.server.stats.metric_snaps())
        return snaps

    async def telemetry(self, p):
        """Raw telemetry feed for `rt telemetry` / /api/telemetry:
        latest per-source metric snapshots + retained flight dumps.
        Aggregation happens client-side (util/telemetry.py)."""
        now = time.time()
        self._prune_metrics_sources(now)
        sources = {s: v["snapshot"]
                   for s, v in self.metrics_sources.items()}
        # The controller reports itself inline — it has no agent to
        # piggyback on, and its loop lag / RPC stats are exactly what
        # the doctor's stall and convoy finders need to see.
        sources["controller"] = self._self_metric_snaps()
        return {"ts": now,
                "sources": sources,
                "flight": list(self.flight_dumps.values()),
                "profiles": list(self.profile_artifacts)}

    def _prune_metrics_history(self, now: float) -> None:
        """Dead sources must not leak deques under worker churn (the
        same contract metrics_sources keeps)."""
        hist = getattr(self, "_metrics_history", None)
        if not hist:
            return
        horizon = max(self.config.metrics_report_period_s * 6, 30.0)
        for src in [s for s, dq in hist.items()
                    if not dq or now - dq[-1][0] > horizon]:
            del hist[src]

    async def metrics_history(self, p):
        """Per-source time series: {source: [[ts, {metric: value}],
        ...]} (ref: dashboard reporter plane)."""
        hist = getattr(self, "_metrics_history", {})
        self._prune_metrics_history(time.time())
        want = (p or {}).get("source")
        out = {}
        for src, dq in hist.items():
            if want and src != want:
                continue
            out[src] = [[ts, vals] for ts, vals in dq]
        return out

    async def metrics_text(self, _p):
        from ray_tpu.util.metrics import render_prometheus

        now = time.time()
        self._prune_metrics_sources(now)
        self._prune_metrics_history(now)
        sources = {s: v["snapshot"]
                   for s, v in self.metrics_sources.items()}
        # Controller-internal gauges, rendered with the same pipeline.
        alive = sum(1 for n in self.nodes.values() if n.alive)
        internal = [
            {"name": "rt_nodes_alive", "kind": "gauge",
             "description": "Alive node agents.",
             "series": [{"tags": {}, "value": alive}]},
            {"name": "rt_nodes_total", "kind": "gauge",
             "description": "Ever-registered node agents.",
             "series": [{"tags": {}, "value": len(self.nodes)}]},
            {"name": "rt_actors", "kind": "gauge",
             "description": "Actors by state.",
             "series": [{"tags": {"state": s},
                         "value": sum(1 for a in self.actors.values()
                                      if a.state == s)}
                        for s in ("ALIVE", "PENDING", "RESTARTING",
                                  "DEAD")]},
            {"name": "rt_tasks_recorded", "kind": "gauge",
             "description": "Task records retained.",
             "series": [{"tags": {}, "value": len(self.task_records)}]},
            {"name": "rt_objects_tracked", "kind": "gauge",
             "description": "Objects in the cluster directory.",
             "series": [{"tags": {}, "value": len(self.object_dir)}]},
        ]
        internal.extend(self._self_metric_snaps())
        sources["controller"] = internal
        return {"text": render_prometheus(sources)}

    async def register_job(self, p):
        jid = self.job_counter
        self.job_counter += 1
        self.jobs[jid] = {"start": time.time(), "driver": p.get("driver", ""),
                          "alive": True,
                          # Link to the multi-tenant job plane: the
                          # submitted job's entrypoint driver carries
                          # its RT_JOB_ID here, so leases/PGs tagged
                          # with the internal job hex resolve to the
                          # tenant for quota/priority/attribution.
                          "tenant": p.get("tenant", "")}
        self._mark_dirty()
        return {"job_id": jid}

    async def finish_job(self, p):
        job = self.jobs.get(p["job_id"])
        if job:
            job["alive"] = False
            self._mark_dirty()
        # Non-detached actors die with their job's driver (ref:
        # gcs_actor_manager.cc OnJobFinished -> DestroyActor) — without
        # this, every connect-and-disconnect driver leaks its actors'
        # workers and their CPU leases into the shared cluster.
        from .ids import JobID

        jid = JobID.from_int(p["job_id"])
        reaped = 0
        for actor in list(self.actors.values()):
            spec = actor.creation_spec
            if actor.detached or spec is None or actor.state == DEAD:
                continue
            if spec.job_id == jid:
                await self.kill_actor({"actor_id": actor.actor_id,
                                       "no_restart": True})
                reaped += 1
        if reaped:
            logger.info("job %s finished: reaped %d actors",
                        p["job_id"], reaped)
        return {"ok": True, "actors_reaped": reaped}

    # ------------------------------------------------------ placement groups
    async def create_placement_group(self, p):
        return await self._placement.create(p)

    async def remove_placement_group(self, p):
        return await self._placement.remove(p)

    async def get_placement_group(self, p):
        return self._placement.get(p)

    async def list_placement_groups(self, p):
        return self._placement.list_all(p)

    # -------------------------------------------------------------- lifetime
    async def ping(self, _p):
        return {"ok": True, "session": self.session,
                "time": time.time()}

    async def cluster_shutdown(self, _p):
        for node in self.nodes.values():
            cli = await self._agent(node.node_id)
            if cli is not None:
                try:
                    await cli.notify("shutdown", {})
                except RpcError:
                    pass
        asyncio.get_event_loop().call_later(0.2, self._shutdown.set)
        return {"ok": True}

    async def run(self, port: int = 0, driver_pid: int = 0) -> int:
        from .placement import PlacementGroupManager

        self._placement = PlacementGroupManager(self)
        if self.config.controller_persistence_enabled:
            self._snapshot_path = os.path.join(
                self.config.session_dir_root, self.session,
                "controller_state.pkl")
            self._load_snapshot()
            spawn_task(self._persist_loop())
        await self.server.start(port)
        # Event-loop lag sampler: the controller loop stalling is the
        # single worst control-plane failure mode (every RPC convoys
        # behind it), so it self-measures like workers/agents do.
        from ray_tpu.util.hotpath import LoopLagSampler

        self._loop_lag = LoopLagSampler(asyncio.get_event_loop())
        self._loop_lag.start()
        spawn_task(self._health_loop())
        spawn_task(self._job_preemption_loop())
        if driver_pid:
            spawn_task(self._watch_driver(driver_pid))
        return self.server.port

    # ------------------------------------------- persistence (GCS FT)
    # Ref: gcs_server.h:113 StorageType + Redis-backed tables; redesigned
    # as a debounced whole-state snapshot — controller state at TPU-host
    # granularity is kilobytes, so one atomic pickle beats a table store.
    def _mark_dirty(self) -> None:
        self._dirty = True

    _PERSIST_CHANNELS = ("actor", "node", "kv", "placement_group",
                         "object_lost")

    def _snapshot_state(self) -> Dict[str, Any]:
        pgs = []
        if self._placement is not None:
            for e in self._placement._groups.values():
                pgs.append({
                    "pg_id": e.pg_id, "bundles": e.bundles,
                    "strategy": e.strategy, "state": e.state,
                    "name": e.name, "placement": dict(e.placement),
                    "priority": e.priority, "job": e.job,
                    "create_time": e.create_time})
        return {
            "kv": self.kv, "kv_list_counts": self.kv_list_counts,
            "actors": self.actors, "named_actors": self.named_actors,
            "jobs": self.jobs, "job_counter": self.job_counter,
            "job_plane": self.job_plane,
            "preempting": self.preempting,
            "task_records": self.task_records,
            "task_events_dropped": self.task_events_dropped,
            "event_seq": self.event_seq,
            "placement_groups": pgs,
        }

    async def _persist_loop(self) -> None:
        import pickle

        self._dirty = True
        while not self._shutdown.is_set():
            await asyncio.sleep(0.5)
            if not getattr(self, "_dirty", False):
                continue
            self._dirty = False
            try:
                data = pickle.dumps(self._snapshot_state())
                tmp = self._snapshot_path + ".tmp"

                def _write():
                    os.makedirs(os.path.dirname(self._snapshot_path),
                                exist_ok=True)
                    with open(tmp, "wb") as f:
                        f.write(data)
                    os.replace(tmp, self._snapshot_path)

                await asyncio.get_event_loop().run_in_executor(None,
                                                               _write)
            except Exception:
                # Persistence must degrade loudly, not die silently: a
                # frozen snapshot restores arbitrarily stale state.
                logger.exception("controller snapshot failed; retrying "
                                 "next cycle")
                self._dirty = True

    def _load_snapshot(self) -> None:
        import pickle

        try:
            with open(self._snapshot_path, "rb") as f:
                state = pickle.load(f)
        except (OSError, pickle.UnpicklingError, EOFError):
            return
        self.kv = state["kv"]
        self.kv_list_counts = state["kv_list_counts"]
        self.actors = state["actors"]
        self.named_actors = state["named_actors"]
        self.jobs = state["jobs"]
        self.job_counter = state["job_counter"]
        self.job_plane = state.get("job_plane", {})
        self.preempting = state.get("preempting", {})
        self.task_records = state["task_records"]
        self.task_events_dropped = state["task_events_dropped"]
        # Event history is gone: continue the sequence and mark all of
        # it trimmed, so every live subscriber gets cursor_expired and
        # resyncs instead of silently missing transitions.
        self.event_seq = state["event_seq"]
        for ch in self._PERSIST_CHANNELS:
            self.events_trimmed_to[ch] = self.event_seq
        from .placement import PGEntry

        for rec in state["placement_groups"]:
            entry = PGEntry(pg_id=rec["pg_id"], bundles=rec["bundles"],
                            strategy=rec["strategy"], state=rec["state"],
                            name=rec["name"],
                            priority=rec.get("priority", 0),
                            job=rec.get("job", ""))
            if rec.get("create_time"):
                entry.create_time = rec["create_time"]
            entry.placement = rec["placement"]
            self._placement._groups[rec["pg_id"]] = entry
        # Restored PENDING/RESCHEDULING groups need the admission loop
        # running again (the pre-restart loop died with the process).
        self._placement.kick()
        logger.info("restored controller state: %d actors, %d kv keys, "
                    "%d jobs, %d PGs", len(self.actors), len(self.kv),
                    len(self.jobs), len(state["placement_groups"]))

    async def _watch_driver(self, pid: int) -> None:
        """Head clusters spawned by a driver die with it (atexit handles
        clean exits; this covers SIGKILL so nothing orphans a 1-core
        host).  Clusters started standalone pass no pid and outlive
        drivers the way the reference's do."""
        while not self._shutdown.is_set():
            await asyncio.sleep(2.0)
            try:
                os.kill(pid, 0)
            except OSError:
                logger.warning("owning driver %d is gone; shutting down",
                               pid)
                await self.cluster_shutdown(None)
                return

    async def wait_shutdown(self) -> None:
        await self._shutdown.wait()
        lag = getattr(self, "_loop_lag", None)
        if lag is not None:
            lag.stop()
        await self.server.stop()


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--session", required=True)
    parser.add_argument("--ready-fd", type=int, default=-1)
    parser.add_argument("--driver-pid", type=int, default=0)
    args = parser.parse_args()
    logging.basicConfig(
        level=getattr(logging,
                      os.environ.get("RT_LOG_LEVEL", "INFO").upper(),
                      logging.INFO),
        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    config = RuntimeConfig.from_env()

    async def _run():
        ctl = Controller(config, args.session)
        port = await ctl.run(args.port, driver_pid=args.driver_pid)
        if args.ready_fd >= 0:
            os.write(args.ready_fd, f"{ctl.server.address}\n".encode())
            os.close(args.ready_fd)
        else:
            print(f"CONTROLLER_ADDRESS={ctl.server.address}", flush=True)
        await ctl.wait_shutdown()

    try:
        asyncio.run(_run())
    except KeyboardInterrupt:
        pass
    sys.exit(0)


if __name__ == "__main__":
    main()
