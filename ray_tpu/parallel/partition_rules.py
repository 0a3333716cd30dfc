"""Regex-rule → PartitionSpec engine over named parameter trees.

The GSPMD layout story in two layers: ``parallel/sharding.py`` maps
*logical dimension names* to mesh axes from inside model code; this
module maps *parameter paths* to PartitionSpecs from outside it —
``match_partition_rules([("wte", P("tensor", "fsdp")), ...], params)``
walks a pytree, names every leaf by its slash-joined path, and returns
the spec tree the first matching regex dictates (fmengine/EasyLM
convention, SNIPPETS.md [2]).  The spec tree drives both the sharded
train-state placement and the elastic checkpoint plane
(``train/sharded_checkpoint.py``), which persists specs per leaf so a
checkpoint taken on one mesh can be resharded onto another.

Scalar leaves are never partitioned (they get an empty spec); a leaf no
rule covers raises by default — silent replication of a 2-D weight is
how an "FSDP" run quietly eats one host's HBM — unless the caller
passes an explicit ``default`` spec.
"""

from __future__ import annotations

import math
import re
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

# A rule set is an ordered sequence of (regex, PartitionSpec) pairs;
# first match wins, so put the most specific patterns first.
Rules = Sequence[Tuple[str, Any]]


def tree_paths(tree: Any, sep: str = "/") -> List[str]:
    """Slash-joined leaf names of a pytree, in tree_flatten order."""
    import jax

    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [path_name(path, sep) for path, _leaf in leaves]


def path_name(path: Tuple, sep: str = "/") -> str:
    """Human-readable name of one tree_flatten_with_path key path:
    dict keys and attribute names joined by ``sep`` (the shape rule
    regexes are written against)."""
    parts = []
    for key in path:
        if hasattr(key, "key"):          # DictKey / FlattenedIndexKey
            parts.append(str(key.key))
        elif hasattr(key, "name"):       # GetAttrKey
            parts.append(str(key.name))
        elif hasattr(key, "idx"):        # SequenceKey
            parts.append(str(key.idx))
        else:
            parts.append(str(key))
    return sep.join(parts)


def named_tree_map(fn: Callable[[str, Any], Any], tree: Any,
                   sep: str = "/") -> Any:
    """tree_map where ``fn`` receives (slash-joined-name, leaf) — the
    shape ``match_partition_rules`` and the checkpoint manifest both
    build on (SNIPPETS.md [2])."""
    import jax

    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: fn(path_name(path, sep), leaf), tree)


def _is_scalar(leaf: Any) -> bool:
    shape = getattr(leaf, "shape", None)
    if shape is None:
        return True
    n = 1
    for d in shape:
        n *= d
    return len(shape) == 0 or n == 1


def match_partition_rules(rules: Rules, params: Any, *,
                          default: Any = None, sep: str = "/") -> Any:
    """Pytree of PartitionSpec per leaf of ``params``.

    Scalar (or single-element) leaves get ``PartitionSpec()`` —
    partitioning them is meaningless.  Everything else takes the spec
    of the FIRST rule whose regex ``re.search``-matches the leaf's
    slash-joined path.  An unmatched leaf raises ``ValueError`` naming
    the parameter unless ``default`` is given (pass
    ``PartitionSpec()`` to mean "replicate whatever I forgot").
    """
    from jax.sharding import PartitionSpec as PS

    def get_spec(name: str, leaf: Any):
        if _is_scalar(leaf):
            return PS()
        for rule, spec in rules:
            if re.search(rule, name) is not None:
                return spec
        if default is not None:
            return default
        raise ValueError(f"partition rule not found for param: {name}")

    return named_tree_map(get_spec, params, sep=sep)


# --------------------------------------------------- spec (de)serialize
def spec_to_json(spec: Any) -> List:
    """PartitionSpec → JSON-able list: each entry None | str |
    [str, ...] (the checkpoint manifest's on-disk spec encoding)."""
    out: List = []
    for entry in tuple(spec):
        if entry is None:
            out.append(None)
        elif isinstance(entry, (tuple, list)):
            out.append([str(a) for a in entry])
        else:
            out.append(str(entry))
    return out


def spec_from_json(data: Optional[Sequence]) -> Any:
    from jax.sharding import PartitionSpec as PS

    if not data:
        return PS()
    entries = []
    for entry in data:
        if entry is None:
            entries.append(None)
        elif isinstance(entry, (tuple, list)):
            entries.append(tuple(entry))
        else:
            entries.append(str(entry))
    return PS(*entries)


def prune_spec(spec: Any, axis_sizes: Dict[str, int],
               shape: Optional[Sequence[int]] = None) -> Any:
    """Drop mesh axes a smaller/renamed mesh no longer has (or has at
    size 1) from a spec — how a checkpoint saved under
    ``P('fsdp', 'tensor')`` restores onto a mesh with no ``tensor``
    axis: the dim simply stops being partitioned.  With ``shape``, an
    axis is also dropped from a dim it does not divide (GPT-2's vocab
    of 50257 over ``tensor=2``): that dim stays whole instead of the
    placement failing."""
    from jax.sharding import PartitionSpec as PS

    entries = []
    for i, entry in enumerate(tuple(spec)):
        if entry is None:
            entries.append(None)
            continue
        axes = entry if isinstance(entry, (tuple, list)) else (entry,)
        kept = [a for a in axes if axis_sizes.get(a, 1) > 1]
        if shape is not None:
            while kept and shape[i] % math.prod(
                    axis_sizes[a] for a in kept):
                kept.pop()
        if not kept:
            entries.append(None)
        elif len(kept) == 1:
            entries.append(kept[0])
        else:
            entries.append(tuple(kept))
    while entries and entries[-1] is None:
        entries.pop()
    return PS(*entries)


def tree_shardings(mesh, spec_tree: Any) -> Any:
    """NamedSharding per leaf of a spec tree (SNIPPETS.md [3]); specs
    are pruned to the mesh's nontrivial axes first so a spec written
    for a bigger mesh stays valid."""
    from jax.sharding import NamedSharding

    import jax

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, prune_spec(spec, sizes)),
        spec_tree)


def shard_tree(tree: Any, mesh, rules: Rules, *, default: Any = None):
    """device_put every leaf under the sharding its matching rule
    dictates — the one-call path from a host param tree to an
    fsdp/tensor-sharded device tree."""
    import jax

    specs = match_partition_rules(rules, tree, default=default)
    shardings = tree_shardings(mesh, specs)
    return jax.tree_util.tree_map(
        lambda leaf, s: jax.device_put(leaf, s), tree, shardings)
