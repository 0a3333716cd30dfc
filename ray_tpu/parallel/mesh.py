"""Device mesh construction with standard parallelism axes.

TPU-native design (no reference counterpart — the reference has no mesh
concept; its parallelism is process groups).  Axis vocabulary follows the
scaling playbook: ``data`` (DP), ``fsdp`` (sharded optimizer/params over
DCN or ICI), ``tensor`` (TP over ICI), ``seq`` (context/sequence
parallel), ``pipeline`` (PP), ``expert`` (MoE).  ``gang_mesh`` is the
one constructor: it takes the axes a job uses, by name, and lays the
devices out in process-major C order (its docstring says why not
``jax.experimental.mesh_utils``).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

# The one vocabulary of mesh axis names (``gang_mesh`` takes no other):
# the partition rules and the activation table (parallel/sharding.py)
# are written in it.  ``dcn`` is the outermost (slowest) axis:
# data-parallel replicas across TPU SLICES communicate over the
# data-center network, while every axis to its right stays inside a
# slice on ICI (ref: the multi-slice mesh recipe — gradient all-reduce
# hierarchically: ICI within a slice, DCN across slices).
AXIS_ORDER = ("dcn", "data", "fsdp", "expert", "pipeline", "seq",
              "tensor")


def process_contiguous_devices() -> List:
    """Global devices in process-major order (all of process 0, then
    process 1, ...).  jax.devices() is already sorted this way, but
    the multi-host training plane's slice math DEPENDS on it, so the
    ordering is enforced here rather than assumed."""
    import jax

    return sorted(jax.devices(),
                  key=lambda d: (d.process_index, d.id))


def gang_mesh(axis_sizes: Dict[str, int],
              devices: Optional[Sequence] = None):
    """Process-contiguous mesh over a gang: a plain C-order reshape of
    the process-major device list into the named ``axis_sizes``
    (insertion order = slowest..fastest varying).

    Deliberately NOT ``mesh_utils.create_device_mesh``: its topology
    optimization may permute devices, and the multi-host training
    plane needs rank r's devices to occupy a CONTIGUOUS block of
    flattened mesh coordinates — the invariant that makes per-rank
    global-batch slices and the sharded checkpoint plane's
    ``coords_for_rank`` agree with the mesh.  On real TPU slices,
    process-major C-order already lands the fastest (rightmost) axis
    on intra-host ICI, which is what the default fsdp x tensor policy
    wants."""
    import numpy as np
    from jax.sharding import Mesh

    names = tuple(axis_sizes)
    unknown = [a for a in names if a not in AXIS_ORDER]
    if unknown:
        raise ValueError(
            f"mesh axes {unknown} are not in the vocabulary {AXIS_ORDER}: "
            "no partition rule or activation rule would ever name them")
    if devices is None:
        devices = process_contiguous_devices()
    devices = list(devices)
    shape = tuple(int(axis_sizes[a]) for a in names)
    n = 1
    for s in shape:
        n *= s
    if n != len(devices):
        raise ValueError(
            f"mesh {dict(axis_sizes)} needs {n} devices, gang has "
            f"{len(devices)}")
    return Mesh(np.array(devices, dtype=object).reshape(shape), names)
