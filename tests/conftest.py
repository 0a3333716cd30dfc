"""Test configuration.

Forces JAX onto a virtual 8-device CPU platform BEFORE any jax import, so
multi-chip sharding paths (mesh, collectives, ring attention, pipeline) are
exercised hermetically on one host — the TPU-era analogue of the
reference's single-machine multi-raylet Cluster fixture (ref:
python/ray/cluster_utils.py:135).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Force the CPU platform with 8 virtual devices (shared recipe).
# ray_tpu import is jax-free, so this runs before jax is imported and
# reaches child worker processes too.
from ray_tpu._virtual_mesh import apply_cpu_mesh_env  # noqa: E402

apply_cpu_mesh_env(os.environ, 8)
# Tier-1 neither reads nor writes XLA's persistent compile cache: the
# runtime would otherwise default it to .jax_cache/ in the checkout
# (ray_tpu/util/compile_cache.py), and a CPU program loaded from there
# is not the program the test compiled.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import pytest  # noqa: E402


@pytest.fixture
def local_runtime():
    """In-process synchronous runtime (reference: local_mode)."""
    import ray_tpu

    rt = ray_tpu.init(mode="local", ignore_reinit_error=False)
    yield rt
    ray_tpu.shutdown()


@pytest.fixture
def cluster_runtime():
    """Single-node multiprocess runtime (controller + agent + workers)."""
    import ray_tpu

    rt = ray_tpu.init(mode="cluster", num_cpus=4)
    yield rt
    ray_tpu.shutdown()


@pytest.fixture(params=["local", "cluster"])
def any_runtime(request):
    """Run a semantics test against both backends."""
    import ray_tpu

    kwargs = {"num_cpus": 4} if request.param == "cluster" else {}
    rt = ray_tpu.init(mode=request.param, **kwargs)
    yield rt
    ray_tpu.shutdown()
