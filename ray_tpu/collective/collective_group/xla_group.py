"""XLA device collective group — the NCCL replacement for TPU.

Role-equivalent to the reference's nccl_collective_group (ref:
python/ray/util/collective/collective_group/nccl_collective_group.py, with
unique-id rendezvous via a named actor at collective.py:151), redesigned
for the TPU execution model: instead of driving a communicator per tensor,
the group bootstraps ``jax.distributed`` across the member processes
(coordinator address exchanged through the rendezvous store) and exposes

- eager host-level collectives (this file) for control tensors and
  weight sync — compiled jax programs over the global device mesh; and
- the *in-graph* path: ``global_mesh()`` hands the caller a
  jax.sharding.Mesh spanning every member's chips, so training steps
  express collectives as mesh axes (psum/all_gather inside pjit) riding
  ICI — the actual TPU hot path (see ray_tpu.parallel).

One jax.distributed world per process: every XLA group in a process must
agree on (world_size, rank); the first initializes, later ones attach.
"""

from __future__ import annotations

import time
from typing import Any, List, Optional

import numpy as np

from .. import telemetry as _telemetry
from ..types import ReduceOp

_initialized_world = None  # (world_size, rank) after jax.distributed init


def _ensure_jax_world(store, group_name: str, world_size: int,
                      rank: int) -> None:
    global _initialized_world
    if _initialized_world is not None:
        if _initialized_world != (world_size, rank):
            raise RuntimeError(
                f"jax.distributed already initialized as "
                f"{_initialized_world}, group {group_name!r} wants "
                f"{(world_size, rank)}")
        return
    import jax

    if world_size == 1:
        _initialized_world = (1, 0)
        return
    # Multi-process CPU worlds (the CI backend) need the CPU client
    # created WITH a cross-process collectives implementation, or every
    # computation spanning processes fails with "Multiprocess
    # computations aren't implemented on the CPU backend".  gloo is
    # compiled into jaxlib; the flag only affects CPU client creation,
    # so it is harmless on TPU.  Must happen before the first backend
    # touch — the client is built lazily on first jax.devices().
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    key = f"col/{group_name}/coordinator"
    # Entry-stamped as gang op #0 (the regular collectives start at
    # seq 1): while a rank sits inside the rendezvous — waiting for
    # the coordinator address, or blocked in jax.distributed.initialize
    # on peers that never arrived — the worker flush loop ships the
    # stamp, and `rt doctor`'s find_distributed_init_stall names the
    # missing ranks once RT_DIST_INIT_TIMEOUT_S passes.
    with _telemetry.timed_op("distributed_init", "xla", world_size,
                             group_name=group_name, rank=rank,
                             seq=0):
        if rank == 0:
            import socket

            from ray_tpu.core.net import get_node_ip_address

            s = socket.socket()
            s.bind(("", 0))
            port = s.getsockname()[1]
            s.close()
            coord = f"{get_node_ip_address()}:{port}"
            store.set(key, coord)
        else:
            deadline = time.time() + 120
            while True:
                coord = store.get(key)
                if coord:
                    break
                if time.time() > deadline:
                    raise TimeoutError(
                        "coordinator address never appeared")
                time.sleep(0.02)
        jax.distributed.initialize(coordinator_address=coord,
                                   num_processes=world_size,
                                   process_id=rank)
    _initialized_world = (world_size, rank)


class XLAGroup:
    def __init__(self, group_name: str, world_size: int, rank: int, store):
        self.group_name = group_name
        self.world_size = world_size
        self.rank = rank
        _ensure_jax_world(store, group_name, world_size, rank)
        import jax

        self._jax = jax
        self.devices = jax.devices()  # global across member processes
        # Gang-op sequence for the collective-entry watchdog (same
        # SPMD lockstep contract as the cpu backend).
        self._gang_seq = 0

    def _gang_op(self, op: str, nbytes: int = 0):
        self._gang_seq += 1
        return _telemetry.timed_op(op, "xla", self.world_size, nbytes,
                                   group_name=self.group_name,
                                   rank=self.rank, seq=self._gang_seq)

    # ------------------------------------------------------------ in-graph
    def global_mesh(self, axis_name: str = "x"):
        """A 1-D mesh over every device in the group — the handle training
        code uses to express collectives as sharding axes (the TPU hot
        path; eager ops below are for control tensors)."""
        from jax.sharding import Mesh

        return Mesh(np.array(self.devices), (axis_name,))

    # -------------------------------------------------------------- eager
    def _gather_all(self, array: np.ndarray) -> List[np.ndarray]:
        from jax.experimental import multihost_utils

        stacked = multihost_utils.process_allgather(np.asarray(array))
        return [np.asarray(s) for s in stacked]

    def _allreduce(self, arr: np.ndarray, op: ReduceOp) -> np.ndarray:
        """Untimed core — reducescatter composes on this so the
        composite op records ONE telemetry sample."""
        parts = self._gather_all(arr)
        out = np.array(parts[0], copy=True)
        for p in parts[1:]:
            if op in (ReduceOp.SUM, ReduceOp.MEAN):
                out += p
            elif op == ReduceOp.PRODUCT:
                out *= p
            elif op == ReduceOp.MAX:
                np.maximum(out, p, out=out)
            elif op == ReduceOp.MIN:
                np.minimum(out, p, out=out)
        if op == ReduceOp.MEAN:
            out = out / len(parts)
        return out

    def allreduce(self, array, op: ReduceOp = ReduceOp.SUM):
        arr = np.asarray(array)
        with self._gang_op("allreduce", arr.nbytes):
            return self._allreduce(arr, op)

    def allgather(self, array) -> List[np.ndarray]:
        arr = np.asarray(array)
        with self._gang_op("allgather", arr.nbytes):
            return self._gather_all(arr)

    def reducescatter(self, array, op: ReduceOp = ReduceOp.SUM):
        arr = np.asarray(array)
        with self._gang_op("reducescatter", arr.nbytes):
            total = self._allreduce(arr, op)
            return np.array_split(total, self.world_size,
                                  axis=0)[self.rank]

    def broadcast(self, array, src_rank: int = 0):
        from jax.experimental import multihost_utils

        arr = np.asarray(array)
        with self._gang_op("broadcast", arr.nbytes):
            return np.asarray(multihost_utils.broadcast_one_to_all(
                arr, is_source=self.rank == src_rank))

    def barrier(self) -> None:
        from jax.experimental import multihost_utils

        with self._gang_op("barrier"):
            multihost_utils.sync_global_devices(
                f"rt_barrier_{self.group_name}")

    def send(self, array, dst_rank: int) -> None:
        raise NotImplementedError(
            "point-to-point on the XLA backend is expressed in-graph via "
            "ppermute over a mesh axis (see ray_tpu.parallel); use the "
            "cpu backend for host p2p")

    def recv(self, src_rank: int, timeout: float = 120.0):
        raise NotImplementedError(
            "point-to-point on the XLA backend is expressed in-graph via "
            "ppermute over a mesh axis (see ray_tpu.parallel); use the "
            "cpu backend for host p2p")

    def destroy(self) -> None:
        pass  # the jax world outlives groups by design
