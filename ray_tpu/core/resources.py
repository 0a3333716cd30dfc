"""Resource accounting and TPU accelerator detection.

Role-equivalent to the reference's scheduling resource model plus its
pluggable accelerator managers (ref: src/ray/common/scheduling/,
python/ray/_private/accelerators/tpu.py).  Resources are float-valued named
capacities; "CPU", "TPU", and "memory" are predefined.  TPU detection reads
/dev/accel* and vfio device nodes the way the reference's
TPUAcceleratorManager does (never JAX: that would make the agent the chip's
owner), and publishes pod/topology extra resources so multi-host slices can
gang-schedule with node affinity.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, Optional

CPU = "CPU"
TPU = "TPU"
MEMORY = "memory"
OBJECT_STORE_MEMORY = "object_store_memory"

_EPS = 1e-9


@dataclass
class ResourceSet:
    """A bag of named float capacities with vector arithmetic."""

    amounts: Dict[str, float] = field(default_factory=dict)

    def __post_init__(self):
        self.amounts = {k: float(v) for k, v in self.amounts.items() if v}

    def get(self, name: str) -> float:
        return self.amounts.get(name, 0.0)

    def is_empty(self) -> bool:
        return not self.amounts

    def covers(self, demand: "ResourceSet") -> bool:
        return all(self.get(k) + _EPS >= v for k, v in demand.amounts.items())

    def add(self, other: "ResourceSet") -> "ResourceSet":
        out = dict(self.amounts)
        for k, v in other.amounts.items():
            out[k] = out.get(k, 0.0) + v
        return ResourceSet(out)

    def subtract(self, other: "ResourceSet") -> "ResourceSet":
        out = dict(self.amounts)
        for k, v in other.amounts.items():
            nv = out.get(k, 0.0) - v
            if nv < -_EPS:
                raise ValueError(f"Resource {k} would go negative: {nv}")
            if abs(nv) < _EPS:
                out.pop(k, None)
            else:
                out[k] = nv
        return ResourceSet(out)

    def utilization(self, total: "ResourceSet") -> float:
        """Max fractional usage across resources present in `total`."""
        best = 0.0
        for k, cap in total.amounts.items():
            if cap > 0:
                used = cap - self.get(k)
                best = max(best, used / cap)
        return best

    def copy(self) -> "ResourceSet":
        return ResourceSet(dict(self.amounts))

    def __repr__(self):
        return f"ResourceSet({self.amounts})"


@dataclass
class TPUInfo:
    num_chips: int
    accelerator_type: str  # e.g. "v5e"; "" where the host does not say
    topology: str  # e.g. "2x4"
    pod_name: Optional[str] = None
    worker_id: int = 0


# PCI ids of TPU chips as the host shows them (vendor 0x1ae0 is Google).
# Only ids read off a real host are listed: 0x0063 on a v5e (PR 21).
_GOOGLE_PCI_VENDOR = "0x1ae0"
_TPU_PCI_DEVICE_GEN = {"0x0063": "v5e"}


def _chip_device_nodes() -> list:
    """The chips' device nodes.  A v5e host shows one ``/dev/vfio/<n>``
    per chip beside the ``/dev/vfio/vfio`` control node (a one-chip
    machine shows e.g. ``/dev/vfio/2`` alone: the name is an IOMMU
    group, not a chip index); older hosts show ``/dev/accel<n>``."""
    nodes = glob.glob("/dev/accel*")
    if not nodes:
        nodes = [v for v in glob.glob("/dev/vfio/*")
                 if os.path.basename(v).isdigit()]
    return sorted(nodes)


def _pci_generation() -> str:
    """The chip generation read from the PCI bus, "" where unknown."""
    for dev in glob.glob("/sys/bus/pci/devices/*"):
        try:
            with open(os.path.join(dev, "vendor")) as f:
                if f.read().strip() != _GOOGLE_PCI_VENDOR:
                    continue
            with open(os.path.join(dev, "device")) as f:
                gen = _TPU_PCI_DEVICE_GEN.get(f.read().strip())
        except OSError:
            continue
        if gen:
            return gen
    return ""


def detect_tpu(override_chips: int = 0) -> Optional[TPUInfo]:
    """Detect local TPU chips without touching them.

    Counts device nodes the way the reference's TPUAcceleratorManager
    does (ref: python/ray/_private/accelerators/tpu.py:97-110) and
    reads GCE TPU env when present.  It never asks JAX: the first
    process to initialise a backend owns the chip, and that must be a
    leased worker, not the node agent.
    """
    chips = override_chips or len(_chip_device_nodes())
    if chips == 0:
        return None
    accel = os.environ.get("TPU_ACCELERATOR_TYPE") or _pci_generation()
    topology = os.environ.get("TPU_TOPOLOGY", "")
    pod = os.environ.get("TPU_NAME") or os.environ.get("TPU_WORKER_HOSTNAMES")
    worker_id = int(os.environ.get("TPU_WORKER_ID", "0") or 0)
    return TPUInfo(chips, accel, topology, pod, worker_id)


def node_resources(
    num_cpus: Optional[float] = None,
    num_tpus: Optional[float] = None,
    memory: Optional[float] = None,
    object_store_memory: Optional[float] = None,
    extra: Optional[Dict[str, float]] = None,
    tpu_override_chips: int = 0,
) -> ResourceSet:
    """Build the resource set a node advertises, with autodetection."""
    amounts: Dict[str, float] = {}
    amounts[CPU] = float(num_cpus if num_cpus is not None else os.cpu_count() or 1)
    if num_tpus is not None:
        if num_tpus:
            amounts[TPU] = float(num_tpus)
    else:
        info = detect_tpu(tpu_override_chips)
        if info:
            amounts[TPU] = float(info.num_chips)
            # Pod-level gang-scheduling labels, as resource entries the way the
            # reference exposes TPU-{type}-{topology}-head (ref: tpu.py:230,330).
            if info.topology and info.accelerator_type:
                amounts[f"TPU-{info.accelerator_type}-{info.topology}-head"] = (
                    1.0 if info.worker_id == 0 else 0.0
                )
    if memory is not None:
        amounts[MEMORY] = float(memory)
    if object_store_memory is not None:
        amounts[OBJECT_STORE_MEMORY] = float(object_store_memory)
    if extra:
        amounts.update({k: float(v) for k, v in extra.items()})
    return ResourceSet({k: v for k, v in amounts.items() if v})


def task_resources(
    num_cpus: Optional[float] = None,
    num_tpus: Optional[float] = None,
    memory: Optional[float] = None,
    resources: Optional[Dict[str, float]] = None,
    default_cpus: float = 1.0,
) -> ResourceSet:
    amounts: Dict[str, float] = {}
    amounts[CPU] = float(default_cpus if num_cpus is None else num_cpus)
    if num_tpus:
        amounts[TPU] = float(num_tpus)
    if memory:
        amounts[MEMORY] = float(memory)
    if resources:
        amounts.update({k: float(v) for k, v in resources.items()})
    return ResourceSet({k: v for k, v in amounts.items() if v})
