"""Multi-host training plane: gang meshes, sharded state, global batches.

The integration layer that takes `JaxTrainer` from single-process to a
gang-scheduled multi-process mesh (ISSUE 15 / ROADMAP "training half"):

**Gang bootstrap.**  ``setup_distributed_mesh`` runs inside each rank's
train loop: rank 0 is the coordinator (its address rendezvouses through
the controller KV via the collective library's XLA group — the gang IS
an XLA collective group named ``train/<attempt_id>``), every rank joins
``jax.distributed``, and the global device view is laid out as one
``Mesh`` with ``fsdp``/``tensor`` axes derived from the gang topology
(CPU multi-process backend in CI, TPU ICI in production — same code).

**Process-contiguous layout invariant.**  Devices enter the mesh in
process-major order and the mesh is a C-order reshape, so rank r's
devices occupy a CONTIGUOUS block of flattened mesh coordinates.  That
single invariant is what makes three independent pieces of math agree:

- ``mesh_coords_for_rank`` here == the sharded checkpoint plane's
  ``coords_for_rank`` (host-mode saves split the same flattened mesh),
- ``global_batch_slice`` (the rows of the global batch a rank feeds)
  lines up with the fsdp rows its devices hold, and
- ``jax.make_array_from_process_local_data`` placement (contiguous
  sub-batch per process) reconstructs the intended global batch.

**Sharded state.**  ``shard_train_state`` drives the GPT-2/Llama
partition-rule sets (``models.*_partition_rules``) through
``match_partition_rules`` over the WHOLE TrainState — optimizer moments
mirror param paths, so one rule set places params and moments alike —
and materializes global jax Arrays under ``NamedSharding`` without any
host-side gather (``make_array_from_callback`` when multi-process).

**Elastic resume.**  Nothing here special-cases restore: the PR-10
sharded checkpoint plane saves the distributed TrainState per-rank
(jax arrays contribute ``addressable_shards``), and a restarted attempt
at ANY world size calls ``setup_distributed_mesh`` +
``session.load_sharded_checkpoint(mesh=..., target=...)`` — the
manifest's slice math reshards N→M.

Pure topology math lives at the top, jax-free at import time, so the
unit tests (and the doctor CLI) never pay a jax import.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

# ===================================================================
# pure topology math (no jax — unit-testable, import-light)
# ===================================================================


def derive_mesh_shape(num_hosts: int, devices_per_host: int, *,
                      fsdp: Optional[int] = None,
                      tensor: Optional[int] = None
                      ) -> Dict[str, int]:
    """fsdp/tensor axis sizes from the gang topology.

    Default policy: the ``tensor`` axis stays INSIDE a host (ICI-
    adjacent on TPU — cross-host tensor parallelism pays DCN latency
    per matmul), so multi-host gangs get ``tensor=devices_per_host``
    and shard everything else over ``fsdp``; a single host defaults to
    pure FSDP over its local chips.  Either axis can be pinned
    explicitly; the other is derived; both pinned is validated.
    """
    if num_hosts < 1 or devices_per_host < 1:
        raise ValueError(
            f"invalid gang topology: {num_hosts} hosts x "
            f"{devices_per_host} devices")
    total = num_hosts * devices_per_host
    if fsdp is None and tensor is None:
        tensor = devices_per_host if num_hosts > 1 else 1
        fsdp = total // tensor
    elif fsdp is None:
        if tensor < 1 or total % tensor:
            raise ValueError(
                f"tensor={tensor} does not divide {total} devices")
        fsdp = total // tensor
    elif tensor is None:
        if fsdp < 1 or total % fsdp:
            raise ValueError(
                f"fsdp={fsdp} does not divide {total} devices")
        tensor = total // fsdp
    if fsdp * tensor != total:
        raise ValueError(
            f"mesh fsdp={fsdp} x tensor={tensor} needs "
            f"{fsdp * tensor} devices, gang has {total}")
    return {"fsdp": fsdp, "tensor": tensor}


def mesh_coords_for_rank(axis_sizes: Dict[str, int], rank: int,
                         world: int) -> List[Dict[str, int]]:
    """Mesh coordinates owned by rank ``rank`` of ``world`` under the
    process-contiguous layout: the C-order flattened mesh is split into
    ``world`` contiguous blocks (first axis slowest).

    MUST agree with ``sharded_checkpoint.coords_for_rank`` — a
    host-mode sharded save performed on these coordinates restores
    onto a gang mesh built here and vice versa (pinned by unit test).
    """
    if world < 1 or not 0 <= rank < world:
        raise ValueError(f"rank {rank} out of range for world {world}")
    names = list(axis_sizes)
    sizes = [int(axis_sizes[a]) for a in names]
    n = 1
    for s in sizes:
        if s < 1:
            raise ValueError(f"axis sizes must be >= 1, got "
                             f"{axis_sizes}")
        n *= s
    lo = rank * n // world
    hi = (rank + 1) * n // world
    out: List[Dict[str, int]] = []
    for lin in range(lo, hi):
        coord: Dict[str, int] = {}
        rem = lin
        for name, size in zip(reversed(names), reversed(sizes)):
            coord[name] = rem % size
            rem //= size
        out.append({a: coord[a] for a in names})
    return out


def global_batch_slice(global_batch_size: int,
                       mesh_shape: Dict[str, int], rank: int,
                       world: int) -> Tuple[int, int]:
    """[start, stop) rows of the global batch rank ``rank`` feeds when
    the batch dim is sharded along ``fsdp``.

    Derivation: under the process-contiguous layout rank r holds
    devices [r*D/world, (r+1)*D/world); device d sits on fsdp row
    ``d // tensor``; the rank must supply the rows of every fsdp row
    its devices touch.  When ``tensor`` spans processes, ranks sharing
    an fsdp row return IDENTICAL slices (they are replicas along the
    batch dim — `make_array_from_process_local_data` requires replica
    hosts to present identical data).
    """
    F = int(mesh_shape.get("fsdp", 1))
    T = int(mesh_shape.get("tensor", 1))
    D = F * T
    if world < 1 or not 0 <= rank < world:
        raise ValueError(f"rank {rank} out of range for world {world}")
    if D % world:
        raise ValueError(
            f"{D} mesh devices not divisible by world {world}")
    if global_batch_size % F:
        raise ValueError(
            f"global batch {global_batch_size} not divisible by "
            f"fsdp={F}")
    per_rank_devs = D // world
    lo_dev = rank * per_rank_devs
    hi_dev = lo_dev + per_rank_devs
    f_lo = lo_dev // T
    f_hi = (hi_dev - 1) // T + 1
    per_row = global_batch_size // F
    return f_lo * per_row, f_hi * per_row


# ===================================================================
# model rule-set hookup
# ===================================================================

def rules_for_model(name: str):
    """The partition-rule set of a row of ``models.MODEL_FAMILIES``, by
    name (lazy import: this module never pays flax)."""
    from ..models import MODEL_FAMILIES

    key = name.lower().replace("-", "").replace("_", "")
    fam = MODEL_FAMILIES.get(key)
    if fam is None:
        raise KeyError(
            f"no partition-rule set for model {name!r}; known: "
            f"{sorted(MODEL_FAMILIES)}")
    return fam.partition_rules()


# ===================================================================
# jax layer — gang bootstrap, sharded placement, global batches
# ===================================================================

def mesh_axis_sizes(mesh) -> Dict[str, int]:
    """THE ordered {axis: size} mapping of a jax Mesh — ordered as the
    mesh's device array is laid out, which is the order replica-group
    device ids unravel to mesh coordinates (util/xprof.py's
    collective-to-axis attribution) and the order sharded checkpoints
    record as ``mesh_axes``.  One definition so the two planes cannot
    disagree."""
    return dict(zip(mesh.axis_names, mesh.devices.shape))


@dataclass
class DistributedMesh:
    """The gang's resolved mesh plus the topology facts train loops
    need: rank/world for batch slicing, axis sizes for checkpoint
    ``mesh_axes``."""

    mesh: Any
    axis_sizes: Dict[str, int] = field(default_factory=dict)
    rank: int = 0
    world: int = 1
    group_name: str = ""

    def batch_sharding(self):
        """NamedSharding for batches: the activation table's ``batch``
        row on this mesh (``fsdp``, on an fsdp x tensor mesh)."""
        return batch_sharding(self.mesh)

    def batch_slice(self, global_batch_size: int) -> Tuple[int, int]:
        """The rows of the global batch THIS rank feeds."""
        return global_batch_slice(global_batch_size, self.axis_sizes,
                                  self.rank, self.world)

    def coords(self) -> List[Dict[str, int]]:
        """This rank's mesh coordinates (== what a host-mode sharded
        save would assign it)."""
        return mesh_coords_for_rank(self.axis_sizes, self.rank,
                                    self.world)


def setup_distributed_mesh(*, fsdp: Optional[int] = None,
                           tensor: Optional[int] = None,
                           group_name: Optional[str] = None
                           ) -> DistributedMesh:
    """Gang bootstrap, called from INSIDE each rank's train loop.

    World > 1: joins (or creates) the gang's XLA collective group —
    rank 0 publishes the jax.distributed coordinator address through
    the controller KV, every rank rendezvouses (the entry-stamped
    ``distributed_init`` op `rt doctor` watches) — then lays the
    global device view out as a process-contiguous fsdp x tensor mesh.
    World 1 (including an elastic resume landed on one host) never
    touches jax.distributed and meshes over LOCAL devices only.
    """
    import jax

    from . import session as session_mod

    try:
        sess = session_mod.get_session()
        rank, world = sess.world_rank, sess.world_size
        attempt = sess.attempt_id
    except RuntimeError:
        rank, world, attempt = 0, 1, ""

    gname = group_name or (f"train/{attempt}" if attempt else "")
    if world > 1:
        from .. import collective as col

        if not gname:
            gname = "train/default"
        if not col.is_group_initialized(gname):
            col.init_collective_group(world, rank, backend="xla",
                                      group_name=gname)
        from ..parallel.mesh import process_contiguous_devices

        devices = process_contiguous_devices()
        if len(devices) % world:
            raise RuntimeError(
                f"{len(devices)} global devices not divisible by "
                f"world {world}")
        per_host = len(devices) // world
    else:
        # Local devices ONLY: a resumed world-1 attempt may run in a
        # process whose stale jax.distributed view still spans dead
        # peers; the global view must not leak into a 1-host mesh.
        devices = list(jax.local_devices())
        per_host = len(devices)
    shape = derive_mesh_shape(world, per_host, fsdp=fsdp,
                              tensor=tensor)
    from ..parallel.mesh import gang_mesh

    mesh = gang_mesh(shape, devices)
    return DistributedMesh(mesh=mesh, axis_sizes=shape, rank=rank,
                           world=world, group_name=gname)


def batch_sharding(mesh):
    """Where a batch's rows lie on ``mesh``: the activation table's
    ``batch`` row (parallel/sharding.py), the same one the models
    constrain their activations by."""
    from ..parallel.sharding import logical_sharding

    return logical_sharding(mesh, ("batch",))


def state_specs(state: Any, rules, *, default: Any = None) -> Any:
    """PartitionSpec tree over a WHOLE TrainState from a model's rule
    set: scalars (step, optax counts) replicate, optimizer moments
    match because their paths embed the param path (``re.search``)."""
    from ..parallel.partition_rules import match_partition_rules

    return match_partition_rules(rules, state, default=default)


def shard_host_tree(tree: Any, mesh, specs: Any) -> Any:
    """Host tree -> global jax Arrays under the specs' NamedShardings.

    Single-process: plain ``device_put``.  Multi-process: every rank
    holds the full host value (deterministic init), and
    ``make_array_from_callback`` hands each addressable device ONLY
    its slice — no gather, no cross-host transfer; HBM per host stays
    1/fsdp of the model."""
    import jax
    import numpy as np

    from ..parallel.partition_rules import tree_shardings

    shardings = tree_shardings(mesh, specs)
    multiprocess = jax.process_count() > 1

    def put(x, s):
        if not multiprocess:
            return jax.device_put(x, s)
        host = np.asarray(x)
        return jax.make_array_from_callback(
            host.shape, s, lambda idx: host[idx])

    return jax.tree_util.tree_map(put, tree, shardings)


def fitted_state_specs(state: Any, mesh, rules, *,
                       default: Any = None) -> Any:
    """``state_specs`` fitted to THIS mesh and these shapes: an axis
    the mesh lacks, or one that does not divide its dim (GPT-2's vocab
    of 50257 over tensor=2), leaves that dim whole.  These are the
    specs the placed arrays really have, so they are what a manifest
    records.  Works on shapes (``jax.eval_shape``) as on arrays."""
    import jax

    from ..parallel.partition_rules import prune_spec

    sizes = mesh_axis_sizes(mesh)
    return jax.tree_util.tree_map(
        lambda leaf, spec: prune_spec(spec, sizes,
                                      getattr(leaf, "shape", ())),
        state, state_specs(state, rules, default=default))


def shard_train_state(state: Any, mesh, rules, *,
                      default: Any = None) -> Tuple[Any, Any]:
    """Rule-driven NamedSharding placement of a TrainState onto the
    gang mesh; returns ``(sharded_state, specs)`` — the specs double
    as the sharded checkpoint plane's per-leaf manifest specs."""
    specs = fitted_state_specs(state, mesh, rules, default=default)
    return shard_host_tree(state, mesh, specs), specs


def put_global_batch(local_batch: Any, mesh, *,
                     global_batch_size: Optional[int] = None) -> Any:
    """Per-rank batch slice -> ONE global array under
    ``batch_sharding(mesh)`` (``batch_transfer`` says how)."""
    return batch_transfer(batch_sharding(mesh),
                          global_batch_size=global_batch_size)(
                              local_batch)


def batch_transfer(sharding, *,
                   global_batch_size: Optional[int] = None
                   ) -> Callable[[Any], Any]:
    """The ``transfer`` callable ``iter_device_batches(sharding=...)``
    builds: per-batch placement under a NamedSharding target, safe in
    both single- and multi-process worlds.  Single-process:
    device_put.  Multi-process:
    ``make_array_from_process_local_data`` — each host contributes
    only the rows it loaded (``global_batch_slice`` rows), the runtime
    wires them into the global batch with zero host-side gather."""
    import jax

    def transfer(batch):
        if jax.process_count() == 1:
            # device_put maps one sharding over every leaf.
            return jax.device_put(batch, sharding)
        import numpy as np

        def put(x):
            x = np.asarray(x)
            gshape = None
            if global_batch_size is not None:
                gshape = (int(global_batch_size),) + x.shape[1:]
            return jax.make_array_from_process_local_data(
                sharding, x, gshape)

        return jax.tree_util.tree_map(put, batch)

    return transfer


def metrics_to_host(metrics: Dict[str, Any]) -> Dict[str, float]:
    """Fully-replicated step metrics -> python floats every rank can
    report (a multi-process global scalar supports float() only
    because it IS fully replicated)."""
    import numpy as np

    return {k: float(np.asarray(v)) for k, v in metrics.items()}
