"""Logical-axis sharding rules — how an ACTIVATION is laid out.

TPU-native design: model code annotates arrays with *logical* dimension
names ("batch", "seq", "stream", "embed", "mlp", "heads", "vocab"); the
ShardingRules table maps logical names to mesh axes.  Changing the
parallelism strategy = changing the table, not the model.  This fills
the reference's TP/FSDP gap (SURVEY.md §2.3 rows 2-3, delegated there to
DeepSpeed/FSDP integrations).

Weights are described elsewhere, once: a family's partition rules
(``models.*_partition_rules``, regex -> PartitionSpec), placed by
``train.distributed.fitted_state_specs``.  Both sides fit a spec to a
mesh and a shape with the same rule, ``partition_rules.prune_spec``:
``fitted_spec`` below is that rule applied to the table, and everything
that lays out an activation goes through it — the models' constraints,
the ``shard_map`` specs of the flash / ring / Ulysses kernels
(models/attention.py) and the batch's sharding
(``train.distributed.DistributedMesh.batch_sharding``).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple, Union

LogicalAxes = Tuple[Optional[str], ...]

# Default table: batch over data(+fsdp), hidden/head dims over tensor,
# sequence over seq (context parallel).  A row exists because a
# constraint or a kernel's spec reads it.
DEFAULT_RULES: Dict[str, Union[str, Tuple[str, ...], None]] = {
    "batch": ("dcn", "data", "fsdp"),
    "seq": "seq",
    # the residual streams of a token (models/decoder.py Residual: a
    # state [batch, seq, stream, embed]): whole, like its ``embed``
    "stream": None,
    "embed": None,
    "mlp": "tensor",
    "heads": "tensor",
    "vocab": "tensor",
}


@dataclass
class ShardingRules:
    rules: Dict[str, Union[str, Tuple[str, ...], None]] = field(
        default_factory=lambda: dict(DEFAULT_RULES))

    def spec(self, logical: LogicalAxes):
        from jax.sharding import PartitionSpec

        unknown = [n for n in logical if n is not None
                   and n not in self.rules]
        if unknown:
            raise KeyError(f"no sharding rule for logical axes {unknown}")
        return PartitionSpec(*(None if n is None else self.rules[n]
                               for n in logical))

    def fitted_spec(self, mesh, logical: LogicalAxes,
                    shape: Optional[Sequence[int]] = None):
        """The table's spec for these logical names, fitted to THIS mesh
        and (with ``shape``) this array: an axis the mesh lacks or has
        at size 1 is dropped, and so is one that does not divide its
        dim (a vocabulary of 50257, or 25 heads, over tensor=2), which
        then stays whole."""
        from .partition_rules import prune_spec

        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        return prune_spec(self.spec(logical), sizes, shape)


def logical_spec(mesh, logical: LogicalAxes,
                 shape: Optional[Sequence[int]] = None):
    """``ShardingRules().fitted_spec``: the default table's answer."""
    return ShardingRules().fitted_spec(mesh, logical, shape)


def logical_shards(mesh, name: str, size: int) -> int:
    """Into how many shards the default table cuts a dimension of
    ``size`` that carries this logical name on this mesh (1: it stays
    whole, as where no axis of its row divides it)."""
    import math

    spec = logical_spec(mesh, (name,), (size,))
    axes = spec[0] if len(spec) else None      # pruned: no entry left
    if axes is None:
        return 1
    return math.prod(mesh.shape[a] for a in
                     ((axes,) if isinstance(axes, str) else axes))


def logical_sharding(mesh, logical: LogicalAxes,
                     shape: Optional[Sequence[int]] = None):
    """NamedSharding for an array whose dims carry these logical names."""
    from jax.sharding import NamedSharding

    return NamedSharding(mesh, logical_spec(mesh, logical, shape))


def with_logical_constraint(x, logical: LogicalAxes, mesh):
    """In-graph sharding constraint by logical names (use inside jit),
    fitted to ``x``'s shape; without a mesh, ``x`` as it is."""
    import jax

    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, logical_sharding(mesh, logical, x.shape))
