"""ray_tpu — a TPU-native distributed runtime and ML stack.

A brand-new framework with the capabilities of the reference system
(cloudlounger/ray, surveyed in SURVEY.md): tasks, actors, and an object
plane on a controller/agent/worker runtime, plus jax/XLA-native ML
libraries (collectives, GSPMD parallelism, Train, Data, Tune, Serve, RL).

This top-level module is intentionally import-light: it must not import
jax/flax (worker processes start through it on a 1-core host).  ML
subpackages load lazily on attribute access.
"""

import atexit
import os
from typing import Any, Dict, Optional

from .core import runtime as _runtime_mod
from .core.api import (cancel, get, get_actor, get_runtime_context,  # noqa: F401
                       kill, method, put, remote, wait)
from .core.api import ActorClass, ActorHandle, RemoteFunction  # noqa: F401
from .core.config import RuntimeConfig
from .core.errors import *  # noqa: F401,F403
from .core.object_ref import ObjectRef  # noqa: F401

__version__ = "0.1.0"

_LAZY_SUBMODULES = ("train", "data", "tune", "serve", "rl", "collective",
                    "parallel", "models", "ops", "util")


def __getattr__(name: str):
    if name in _LAZY_SUBMODULES:
        import importlib

        mod = importlib.import_module(f"{__name__}.{name}")
        globals()[name] = mod
        return mod
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def init(
    address: Optional[str] = None,
    *,
    mode: str = "auto",
    num_cpus: Optional[float] = None,
    num_tpus: Optional[float] = None,
    resources: Optional[Dict[str, float]] = None,
    object_store_memory: Optional[int] = None,
    namespace: str = "",
    config: Optional[Dict[str, Any]] = None,
    log_to_driver: Optional[bool] = None,
    ignore_reinit_error: bool = False,
):
    """Start (or connect to) a runtime.

    Role-equivalent to the reference's ray.init (ref:
    python/ray/_private/worker.py:1275).

    - ``mode="local"``: synchronous in-process execution (debugging).
    - ``mode="cluster"``: spawn a controller + node agent + workers on this
      host (the default for ``mode="auto"`` unless RT_LOCAL_MODE=1).
    - ``address="<host:port>"``: connect as a driver to an existing cluster.
    """
    if _runtime_mod.is_initialized():
        if ignore_reinit_error:
            return _runtime_mod.get_runtime()
        raise RuntimeError("ray_tpu.init() called twice "
                           "(pass ignore_reinit_error=True to allow)")
    overrides = dict(config or {})
    if object_store_memory:
        overrides["object_store_memory_bytes"] = int(object_store_memory)
    if log_to_driver is not None:
        overrides["log_to_driver"] = log_to_driver
    cfg = RuntimeConfig.from_env(overrides)
    # One XLA compile cache for every process of the runtime: where
    # JAX_COMPILATION_CACHE_DIR says, else the fixed default; the
    # processes this driver starts inherit it.
    from .util import compile_cache

    compile_cache.apply()
    if address and address.startswith("rt://"):
        # Remote driver: one connection to the head's ClientServer; no
        # cluster-routable agent needed on this machine (ref:
        # util/client/ARCHITECTURE.md).
        from .client.runtime import ClientRuntime

        rt = ClientRuntime(cfg, address[len("rt://"):])
        _runtime_mod.set_runtime(rt)
        atexit.register(_shutdown_quiet)
        return rt
    if mode == "auto":
        import importlib.util

        has_cluster = (
            importlib.util.find_spec("ray_tpu.core.cluster_runtime")
            is not None)
        mode = ("local" if os.environ.get("RT_LOCAL_MODE") == "1"
                or not has_cluster else "cluster")
    if mode == "local":
        from .core.local_runtime import LocalRuntime

        rt = LocalRuntime(cfg)
    elif mode == "cluster":
        from .core.cluster_runtime import ClusterRuntime

        if address == "auto":
            from .scripts.cli import resolve_address

            address = resolve_address(cfg)
            if address is None:
                raise ConnectionError(
                    'address="auto" but no running cluster was found on '
                    "this machine (start one with `python -m ray_tpu "
                    "start --head`).")
        rt = ClusterRuntime(
            cfg, address=address, num_cpus=num_cpus, num_tpus=num_tpus,
            custom_resources=resources, namespace=namespace)
    else:
        raise ValueError(f"Unknown mode {mode!r}")
    _runtime_mod.set_runtime(rt)
    atexit.register(_shutdown_quiet)
    return rt


def _shutdown_quiet():
    try:
        shutdown()
    except Exception:
        pass


def shutdown() -> None:
    """Tear down the runtime started by init()."""
    if _runtime_mod.is_initialized():
        rt = _runtime_mod.get_runtime()
        _runtime_mod.set_runtime(None)
        rt.shutdown()


def is_initialized() -> bool:
    return _runtime_mod.is_initialized()


def cluster_resources() -> Dict[str, float]:
    return _runtime_mod.get_runtime().cluster_resources()


def available_resources() -> Dict[str, float]:
    return _runtime_mod.get_runtime().available_resources()


def nodes():
    return _runtime_mod.get_runtime().nodes()


def timeline(filename: Optional[str] = None):
    """Chrome-trace export of recorded task events (ref: ray.timeline,
    python/ray/_private/state.py:960)."""
    from .util import state as _state

    return _state.timeline(filename)
