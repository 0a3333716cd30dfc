"""The one table of chip peak rates, keyed by ``device_kind``.

``jax.devices()[0].device_kind`` is what the runtime actually ran on, so
it is the key; a device that is not in the table is an error, never an
assumed generation.  Jax-free at import (``rt perf`` runs on an ops box
without the ML stack): ``local_device_kind`` only reads a backend that
the process has already initialised and never starts one.

Sources: Google Cloud TPU documentation, system-architecture pages
("TPU v4", "TPU v5e", "TPU v5p", "TPU v6e"), per-chip figures: peak bf16
compute, HBM capacity and bandwidth, and inter-chip interconnect
bandwidth (documented in Gbit/s; stored here in bytes/s).  Only the
``TPU v5 lite`` spelling has been read off a real device (v5e, PR 21);
the other keys follow JAX's naming for those generations.
"""

from __future__ import annotations

import os
import sys
from dataclasses import dataclass
from typing import Dict, Optional


@dataclass(frozen=True)
class ChipPeaks:
    gen: str                     # short generation name, e.g. "v5e"
    flops_per_sec: float         # peak bf16 FLOP/s per chip
    hbm_bytes_per_sec: float     # HBM bandwidth per chip
    ici_bytes_per_sec: float     # inter-chip interconnect per chip
    hbm_bytes: float             # HBM capacity per chip


CHIP_PEAKS: Dict[str, ChipPeaks] = {
    "TPU v4": ChipPeaks("v4", 275e12, 1228e9, 300e9, 32e9),
    "TPU v5 lite": ChipPeaks("v5e", 197e12, 819e9, 200e9, 16e9),
    "TPU v5": ChipPeaks("v5p", 459e12, 2765e9, 600e9, 95e9),
    "TPU v6 lite": ChipPeaks("v6e", 918e12, 1640e9, 400e9, 32e9),
}


class UnknownChipError(LookupError):
    """No peak rates are known for this device (or no device is known).
    Set the ``RT_PEAK_*`` overrides, pass peaks explicitly, or add the
    ``device_kind`` to ``CHIP_PEAKS`` with its source."""


def peaks_for(device_kind: Optional[str]) -> ChipPeaks:
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise UnknownChipError(
            f"device_kind {device_kind!r} is not in the chip peak table "
            f"(known: {sorted(CHIP_PEAKS)}); set RT_PEAK_FLOPS_PER_DEVICE"
            " / RT_PEAK_HBM_BYTES_PER_SEC / RT_INTERCONNECT_BYTES_PER_SEC"
            " or add the device to ray_tpu/util/chips.py") from None


def backend_initialized() -> bool:
    """Has THIS process started a JAX backend?  Never starts one, and
    imports nothing: another thread may be in the middle of importing
    jax, and an import from here would race it for the module locks."""
    xla_bridge = sys.modules.get("jax._src.xla_bridge")
    return bool(getattr(xla_bridge, "_backends", None))


def local_device_kind() -> Optional[str]:
    """``device_kind`` of this process's first device, or None where no
    backend is up (asking would start one, and with it take the chip)."""
    if not backend_initialized():
        return None
    import jax

    return jax.devices()[0].device_kind


def describe_devices() -> Dict[str, object]:
    """What this process computes on, as JAX reports it (starts the
    backend: call it only where the process is meant to own a device)."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "pid": os.getpid()}


def peak_device_memory_bytes() -> Optional[int]:
    """Largest ``peak_bytes_in_use`` any local device's allocator
    reports (None where the backend reports none, as the CPU's).  On
    TPU this leaves out a program's own temporaries."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def resolve_peak(field: str, env_var: str,
                 device_kind: Optional[str] = None) -> float:
    """One peak rate: the ``RT_PEAK_*`` override when set, else the
    table row of ``device_kind`` (default: this process's device)."""
    env = os.environ.get(env_var, "")
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    return getattr(peaks_for(device_kind or local_device_kind()), field)


def max_node_chips() -> float:
    """The largest TPU count any live node of the running cluster
    advertises (0 where none has a chip)."""
    from ..core import runtime as runtime_mod

    rt = runtime_mod.get_runtime()
    if not hasattr(rt, "nodes"):        # local mode: one node
        return float(rt.cluster_resources().get("TPU", 0.0))
    return max((n["Resources"].get("TPU", 0.0) for n in rt.nodes()
                if n.get("Alive", True)), default=0.0)
