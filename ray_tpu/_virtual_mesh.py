"""Env recipe for a virtual n-device CPU platform (hermetic mesh tests).

JAX reads ``JAX_PLATFORMS`` and ``XLA_FLAGS`` when it is imported, so
the edits go into the environment BEFORE that (and so reach child
processes too).  Kept in one place so every user of the virtual mesh
(tests/conftest.py, chip_smoke.py's CPU rehearsal) builds it the same
way.

This module must stay importable without jax.
"""

from __future__ import annotations

import re
from typing import MutableMapping

_FLAG = "--xla_force_host_platform_device_count"


def apply_cpu_mesh_env(env: MutableMapping[str, str],
                       n_devices: int) -> MutableMapping[str, str]:
    """Mutate ``env`` so a fresh interpreter sees an n-device CPU platform.

    Overwrites any stale device-count flag (a leftover =4 from a prior
    recipe must not survive a request for 8 devices).
    """
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    flags = re.sub(rf"{_FLAG}=\S+", "", flags)
    env["XLA_FLAGS"] = f"{flags} {_FLAG}={n_devices}".strip()
    env.setdefault("JAX_ENABLE_X64", "0")
    return env
