"""Logical-axis sharding rules — DP/FSDP/TP expressed as name mappings.

TPU-native design: model code annotates arrays with *logical* dimension
names ("batch", "seq", "embed", "mlp", "heads", "kv", "vocab",
"stage", "expert"); a ShardingRules table maps logical names to mesh
axes.  Changing the parallelism strategy = changing the table, not the
model.  This fills the reference's TP/FSDP gap (SURVEY.md §2.3 rows 2-3,
delegated there to DeepSpeed/FSDP integrations).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple, Union

LogicalAxes = Tuple[Optional[str], ...]

# Default table: batch over data(+fsdp), params sharded over fsdp,
# hidden/head dims over tensor, sequence over seq (context parallel),
# experts over expert.
DEFAULT_RULES: Dict[str, Union[str, Tuple[str, ...], None]] = {
    "batch": ("dcn", "data", "fsdp"),
    "seq": "seq",
    "embed": None,
    "embed_fsdp": "fsdp",       # param embed dim when FSDP-sharding params
    "mlp": "tensor",
    "heads": "tensor",
    "kv": None,
    "head_dim": None,
    "vocab": "tensor",
    "expert": "expert",
    "stage": "pipeline",
}


@dataclass
class ShardingRules:
    rules: Dict[str, Union[str, Tuple[str, ...], None]] = field(
        default_factory=lambda: dict(DEFAULT_RULES))

    def mesh_axes(self, logical: LogicalAxes) -> Tuple:
        out = []
        for name in logical:
            if name is None:
                out.append(None)
            else:
                if name not in self.rules:
                    raise KeyError(f"no sharding rule for logical axis "
                                   f"{name!r}")
                out.append(self.rules[name])
        return tuple(out)

    def spec(self, logical: LogicalAxes):
        from jax.sharding import PartitionSpec

        return PartitionSpec(*self.mesh_axes(logical))

    def prune(self, mesh) -> "ShardingRules":
        """Drop references to axes of size 1 (keeps specs minimal so XLA
        sees fully-replicated dims as such)."""
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        out = {}
        for k, v in self.rules.items():
            if v is None:
                out[k] = None
            elif isinstance(v, tuple):
                kept = tuple(a for a in v if sizes.get(a, 1) > 1)
                out[k] = kept if kept else None
            else:
                out[k] = v if sizes.get(v, 1) > 1 else None
        return ShardingRules(out)


def logical_sharding(mesh, logical: LogicalAxes,
                     rules: Optional[ShardingRules] = None):
    """NamedSharding for an array whose dims carry these logical names."""
    from jax.sharding import NamedSharding

    rules = (rules or ShardingRules()).prune(mesh)
    return NamedSharding(mesh, rules.spec(logical))


def with_logical_constraint(x, logical: LogicalAxes, mesh=None,
                            rules: Optional[ShardingRules] = None):
    """In-graph sharding constraint by logical names (use inside jit)."""
    import jax

    rules = rules or ShardingRules()
    if mesh is None:
        from jax.sharding import PartitionSpec

        # Under shard_map/jit with an ambient mesh, bare specs work.
        return jax.lax.with_sharding_constraint(
            x, rules.spec(logical))
    from jax.sharding import NamedSharding

    from .partition_rules import prune_spec

    # Fitted to the array: an axis that does not divide its dim (a
    # vocab of 50257 over tensor=2) leaves that dim unconstrained.
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    spec = prune_spec(logical_sharding(mesh, logical, rules).spec,
                      sizes, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def shard_pytree(tree, mesh, logical_fn, rules=None):
    """Device-put every leaf with the sharding for logical_fn(path, leaf).

    logical_fn: (path_str, leaf) -> tuple of logical axis names (or None
    for replicated).  Used to lay out parameter pytrees.
    """
    import jax

    rules = (rules or ShardingRules()).prune(mesh)

    def _place(path, leaf):
        path_str = jax.tree_util.keystr(path)
        logical = logical_fn(path_str, leaf)
        if logical is None:
            logical = (None,) * getattr(leaf, "ndim", 0)
        return jax.device_put(leaf, logical_sharding(mesh, logical, rules))

    return jax.tree_util.tree_map_with_path(_place, tree)
