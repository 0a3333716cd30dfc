"""WorkerGroup — a gang of training-worker actors.

Role-equivalent to the reference's train worker group (ref:
train/_internal/worker_group.py): N actors created with per-worker
resources (optionally inside a STRICT_SPREAD placement group so each
worker is its own TPU host), ``execute`` fan-out of functions, and death
detection surfaced as WorkerGroupError.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

import ray_tpu
from ..util import PlacementGroupSchedulingStrategy, placement_group, \
    remove_placement_group


class WorkerGroupError(RuntimeError):
    def __init__(self, rank: int, cause: BaseException):
        self.rank = rank
        self.cause = cause
        super().__init__(f"training worker {rank} failed: {cause!r}")


# Exception types that recur on every attempt when raised by USER code
# inside the train loop: retrying burns the whole max_failures budget
# (and the TPU-hours behind it) on an error a stack trace already
# explains.  Infra errors never subclass these directly — a remote
# user exception re-raises as a TaskError dual-subclass
# (errors.make_task_error), so isinstance() still identifies them.
DETERMINISTIC_ERRORS = (
    ValueError, TypeError, KeyError, IndexError, AttributeError,
    ZeroDivisionError, AssertionError, NotImplementedError,
)


class PreemptionError(RuntimeError):
    """A training worker was lost to an ANNOUNCED failure: its node
    delivered a preemption/drain notice before dying.  The v2
    controller treats this differently from a crash — the restart does
    not consume a ``FailureConfig.max_failures`` budget slot, because
    preemption frequency is a property of the fleet, not of the job
    (cf. Bamboo NSDI'23 / Gemini SOSP'23 on spot-instance training)."""

    def __init__(self, message: str, node_id: str = "",
                 reason: str = "", cause: BaseException = None):
        super().__init__(message)
        self.node_id = node_id
        self.reason = reason
        self.cause = cause


@ray_tpu.remote
class _TrainWorkerActor:
    """Hosts the user's train loop; one per rank."""

    def __init__(self, rank: int):
        self.rank = rank
        self.env: Dict[str, str] = {}

    def set_env(self, env: Dict[str, str]):
        self.env.update(env)
        os.environ.update(env)
        return True

    def node_id(self) -> str:
        return os.environ.get("RT_NODE_ID", "")

    def run(self, fn_payload: bytes, args: tuple, kwargs: dict):
        import cloudpickle

        fn = cloudpickle.loads(fn_payload)
        return fn(*args, **kwargs)


@dataclass
class WorkerMeta:
    rank: int
    actor: Any
    node_id: str = ""


class WorkerGroup:
    def __init__(self, num_workers: int,
                 resources_per_worker: Optional[Dict[str, float]] = None,
                 placement_strategy: Optional[str] = None,
                 name_prefix: str = "train"):
        t_start = time.monotonic()
        self.num_workers = num_workers
        self._pg = None
        res = dict(resources_per_worker or {"CPU": 1.0})
        opts: Dict[str, Any] = {
            "num_cpus": res.pop("CPU", 1.0),
            "num_tpus": res.pop("TPU", None),
            "resources": res or None,
            "max_concurrency": 2,  # run() + control calls
        }
        if opts["num_tpus"]:
            # A demand no node can ever meet would otherwise sit out
            # the placement timeout (or pend forever as a bare actor).
            from ..util.chips import max_node_chips

            have = max_node_chips()
            if opts["num_tpus"] > have:
                raise RuntimeError(
                    f"each training worker asks for {opts['num_tpus']:g}"
                    f" TPU chip(s) but the largest node has {have:g}; "
                    "not schedulable on this cluster")
        if placement_strategy:
            bundles = []
            for _ in range(num_workers):
                b = {"CPU": opts["num_cpus"]}
                if opts["num_tpus"]:
                    b["TPU"] = opts["num_tpus"]
                if res:
                    b.update(res)
                bundles.append(b)
            self._pg = placement_group(bundles,
                                       strategy=placement_strategy)
            if not self._pg.wait(120):
                remove_placement_group(self._pg)
                raise TimeoutError(
                    f"placement group for {num_workers} training workers "
                    f"({bundles[0]}) not schedulable")
        self.workers: List[WorkerMeta] = []
        for rank in range(num_workers):
            o = dict(opts)
            if self._pg is not None:
                o["scheduling_strategy"] = PlacementGroupSchedulingStrategy(
                    self._pg, rank)
            actor = _TrainWorkerActor.options(**o).remote(rank)
            self.workers.append(WorkerMeta(rank, actor))
        # Resolve node placement for local-rank computation.
        node_ids = ray_tpu.get([w.actor.node_id.remote()
                                for w in self.workers])
        for w, nid in zip(self.workers, node_ids):
            w.node_id = nid
        try:
            from ..util.metrics import Gauge, Histogram

            Histogram("rt_train_worker_group_start_seconds",
                      "Gang placement + actor spawn time for a "
                      "training worker group.").observe(
                time.monotonic() - t_start)
            Gauge("rt_train_workers",
                  "Workers in the most recent training gang.").set(
                float(num_workers))
        except Exception:
            pass

    def local_ranks(self) -> List[Dict[str, int]]:
        """Per-worker local rank/size/node-rank from node placement."""
        by_node: Dict[str, List[int]] = {}
        for w in self.workers:
            by_node.setdefault(w.node_id, []).append(w.rank)
        node_order = sorted(by_node)
        out = []
        for w in self.workers:
            ranks = sorted(by_node[w.node_id])
            out.append({
                "local_rank": ranks.index(w.rank),
                "local_world_size": len(ranks),
                "node_rank": node_order.index(w.node_id),
            })
        return out

    def set_env(self, env: Dict[str, str]) -> None:
        ray_tpu.get([w.actor.set_env.remote(env) for w in self.workers])

    def execute_async(self, fn: Callable, *args, **kwargs) -> List[Any]:
        from ..core import serialization

        payload = serialization.dumps_code(fn)
        return [w.actor.run.remote(payload, args, kwargs)
                for w in self.workers]

    def execute(self, fn: Callable, *args, **kwargs) -> List[Any]:
        return ray_tpu.get(self.execute_async(fn, *args, **kwargs))

    def execute_async_single(self, worker: "WorkerMeta", fn: Callable,
                             *args, **kwargs):
        from ..core import serialization

        payload = serialization.dumps_code(fn)
        return worker.actor.run.remote(payload, args, kwargs)

    def execute_single(self, rank: int, fn: Callable, *args, **kwargs):
        return ray_tpu.get(self.execute_async_single(
            self.workers[rank], fn, *args, **kwargs))

    def shutdown(self) -> None:
        for w in self.workers:
            try:
                ray_tpu.kill(w.actor)
            except Exception:
                pass
        self.workers.clear()
        if self._pg is not None:
            try:
                remove_placement_group(self._pg)
            except Exception:
                pass
            self._pg = None
