"""Gradients compared without shipping them: a sketch of a parameter-shaped
tree, the same function in the leased worker (on the program's first Adam
moments) and in the reference's process (on the reference's gradients).

Per leaf: a vector leaf is kept whole; a matrix ``[in, out]`` becomes
``r @ G`` with ``r`` a fixed standard-normal vector over ``in`` (threefry,
the same numbers on every backend); with it the leaf's Euclidean norm and,
where given, the sum of the matching second-moment leaf.  A leaf computed
with wrong attention mathematics moves its sketch by its own size (PERF.md
has the figures), bf16 arithmetic by a few hundredths of it.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

SKETCH_SEED = 0x736b


def sketch(tree, second=None) -> Dict[str, Dict[str, Any]]:
    """{leaf path: {"v": vector, "norm": scalar, "sq": scalar}}; ``sq`` is
    the sum of ``second``'s leaf, or of the leaf's squares.  Jit it."""
    import jax
    import jax.numpy as jnp

    leaves = jax.tree_util.tree_leaves_with_path(tree)
    seconds = jax.tree_util.tree_leaves(second) if second is not None \
        else [None] * len(leaves)
    out = {}
    for i, ((path, g), s) in enumerate(zip(leaves, seconds)):
        g = g.astype(jnp.float32)
        v = g
        if g.ndim >= 2:
            g2 = g.reshape(g.shape[0], -1)
            key = jax.random.fold_in(jax.random.PRNGKey(SKETCH_SEED), i)
            v = jax.random.normal(key, (g2.shape[0],), jnp.float32) @ g2
        sq = jnp.sum(g * g)
        out[jax.tree_util.keystr(path)] = {
            "v": v, "norm": jnp.sqrt(sq),
            "sq": sq if s is None else jnp.sum(s.astype(jnp.float32))}
    return out


def flatten(sk: Dict[str, Dict[str, Any]]) -> Dict[str, Any]:
    """For ``numpy.savez``: one flat mapping of arrays."""
    import numpy as np

    return {f"{name}|{k}": np.asarray(v)
            for name, leaf in sk.items() for k, v in leaf.items()}


def unflatten(flat) -> Dict[str, Dict[str, Any]]:
    out: Dict[str, Dict[str, Any]] = {}
    for key in flat:
        name, k = key.rsplit("|", 1)
        out.setdefault(name, {})[k] = flat[key]
    return out


def compare(program, reference) -> Dict[str, Any]:
    """Per leaf, how far the program's sketch lies from the reference's as
    a share of the reference's size; the worst and the median leaf."""
    import numpy as np

    if set(program) != set(reference):
        odd = sorted(set(program) ^ set(reference))[:4]
        raise ValueError(f"the two trees have different leaves: {odd}")
    rel, sq_rel = {}, {}
    for name, ref in reference.items():
        got = program[name]
        size = float(np.linalg.norm(ref["v"]))
        rel[name] = float(np.linalg.norm(
            np.asarray(got["v"], np.float64) - ref["v"])) / size \
            if size > 0 else float("inf")
        sq_rel[name] = abs(float(got["sq"]) - float(ref["sq"])) \
            / float(ref["sq"]) if float(ref["sq"]) > 0 else float("inf")
    worst = max(rel, key=rel.get)
    return {"leaves": len(rel), "max_leaf_rel": rel[worst],
            "worst_leaf": worst,
            "median_leaf_rel": float(np.median(list(rel.values()))),
            "max_second_rel": max(sq_rel.values())}


def adam_first_step(grads, b1: float, b2: float, grad_clip: float):
    """What AdamW's moments hold after one update from zero with
    ``optax.clip_by_global_norm(grad_clip)`` before it: ``(1 - b1) g`` and
    ``(1 - b2) g^2`` of the clipped gradient.  Returns (mu, nu, norm of
    the unclipped gradient)."""
    import jax
    import jax.numpy as jnp

    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree_util.tree_leaves(grads)))
    scale = jnp.where(norm < grad_clip, 1.0, grad_clip / norm)
    mu = jax.tree_util.tree_map(lambda g: (1.0 - b1) * scale * g, grads)
    nu = jax.tree_util.tree_map(
        lambda g: (1.0 - b2) * jnp.square(scale * g), grads)
    return mu, nu, norm


def adam_moments(opt_state) -> Optional[Any]:
    """The node of an optax state that holds Adam's ``mu`` and ``nu``."""
    import jax

    found = [n for n in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda n: hasattr(n, "mu") and hasattr(n, "nu"))
        if hasattr(n, "mu") and hasattr(n, "nu")]
    return found[0] if found else None
