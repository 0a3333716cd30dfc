"""The benchmark's own table of chip peaks, keyed by ``device_kind`` as JAX
reports it.  A device that is not in the table is an error, never a
default.  (A copy of the one row of ray_tpu/util/chips.py that has been
read off a real device; later PRs cannot change this copy.)

Source: Google Cloud TPU documentation, "TPU v5e" system architecture:
per chip 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of
inter-chip interconnect (200 GB/s).
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    gen: str
    flops_per_s: float        # bf16
    hbm_bytes_per_s: float
    ici_bytes_per_s: float
    hbm_bytes: float


PEAKS = {
    "TPU v5 lite": Peaks("v5e", 197e12, 819e9, 200e9, 16e9),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise LookupError(
            f"device_kind {device_kind!r} is not in the benchmark's peak "
            f"table (known: {sorted(PEAKS)}); add it to "
            "benchmark/harness/peaks.py with its source") from None
