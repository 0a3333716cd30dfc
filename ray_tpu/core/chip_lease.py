"""Chip ownership of worker processes: one process for each chip lease,
and every other process kept off the chip.

A TPU chip belongs to one process at a time: the first process whose
JAX backend initialises takes libtpu's lock and the device nodes and
keeps them until it exits.  So on a node that has chips

- every worker is SPAWNED kept off them (``JAX_PLATFORMS=cpu``; the
  value the node itself runs under is remembered beside it), whatever
  its tasks import;
- a worker that is GRANTED chips gets that lifted and sees exactly its
  chips, exported before its first backend initialisation.  JAX reads
  ``JAX_PLATFORMS`` when it is imported, not when a backend starts, so a
  pooled worker that imported jax earlier has its config updated too;
- a grant that reaches a worker whose backend is already up cannot take
  effect any more and raises, naming the cause;
- the node agent retires a worker when its chip lease ends (the
  process would hold the chips for as long as it lived).

Bounds were checked against libtpu 0.0.34 on v5e (PR 21): one chip of a
host as ``1,1,1``, two as ``1,2,1``, all four of a 2x2 host as
``2,2,1``; ``2,1,1`` for two chips hangs at start-up.

Jax-free at import, like the rest of ``core/``.
"""

from __future__ import annotations

import os
import sys
from typing import Dict, List, MutableMapping

from ..util.chips import backend_initialized

# Set at spawn on a TPU node: the JAX_PLATFORMS the node agent itself
# runs under ("" = unset, JAX picks), restored when a lease lifts the
# guard.  Its presence is what marks a worker as guarded.
GUARD_ENV = "RT_CHIP_GUARD_PLATFORMS"

_LEASE_VARS = ("TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_PROCESS_BOUNDS",
               "TPU_PROCESS_BOUNDS")
_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1"}


class ChipLeaseError(RuntimeError):
    """A chip lease could not be given to this worker process."""


def guard_spawn_env(env: MutableMapping[str, str], node_chips: int
                    ) -> None:
    """Environment of a worker spawned on a node with ``node_chips``
    chips: no chip until a lease says which."""
    if node_chips <= 0:
        return
    env[GUARD_ENV] = env.get("JAX_PLATFORMS", "")
    env["JAX_PLATFORMS"] = "cpu"
    for var in _LEASE_VARS:      # nothing inherited from the launcher
        env.pop(var, None)


def lease_env(chip_ids: List[int]) -> Dict[str, str]:
    """What libtpu needs to open exactly ``chip_ids`` in one process."""
    try:
        bounds = _BOUNDS[len(chip_ids)]
    except KeyError:
        raise ChipLeaseError(
            f"a lease of {len(chip_ids)} chips has no process bounds "
            f"libtpu is known to accept (known: {sorted(_BOUNDS)})"
        ) from None
    return {"TPU_VISIBLE_CHIPS": ",".join(map(str, sorted(chip_ids))),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": bounds,
            "TPU_PROCESS_BOUNDS": "1,1,1"}


def apply_lease(chip_ids: List[int]) -> None:
    """Give this worker process its chips (no-op for a lease without).

    Every lease variable is overwritten, so nothing survives from an
    earlier lease; the same lease applied again (the next task on it)
    changes nothing."""
    if not chip_ids:
        return
    want = lease_env(chip_ids)
    if backend_initialized():
        if all(os.environ.get(k) == v for k, v in want.items()) \
                and GUARD_ENV not in os.environ:
            return
        raise ChipLeaseError(
            f"chip lease {chip_ids} reached worker pid {os.getpid()} "
            "after it had initialised a JAX backend (platforms "
            f"{_backend_names()}): its devices are fixed and the lease "
            "cannot take effect; chip work must run in a worker that "
            "has not used JAX before")
    os.environ.update(want)
    platforms = os.environ.pop(GUARD_ENV, None)
    if platforms is None:
        return          # not a guarded worker (no chips on this node)
    if platforms:
        os.environ["JAX_PLATFORMS"] = platforms
    else:
        os.environ.pop("JAX_PLATFORMS", None)
    if "jax" in sys.modules:
        import jax

        jax.config.update("jax_platforms", platforms or None)


def _backend_names() -> List[str]:
    return sorted(sys.modules["jax._src.xla_bridge"]._backends)
