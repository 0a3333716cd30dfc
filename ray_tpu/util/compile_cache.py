"""Where XLA's persistent compile cache lives: one place, settable from
outside.

``JAX_COMPILATION_CACHE_DIR`` decides.  Where it is set, every process
of the runtime keeps its cache there (JAX reads the variable itself at
import) and no code sets another.  Where it is not, the driver and the
node agent default it to ONE fixed directory inside the checkout,
``.jax_cache/`` beside the ``ray_tpu`` package, and workers inherit it
through the environment they are spawned with.  The path is part of
the cache key, so it is never made from a temporary name, a pid, a
session name or the time.

Jax-free at import.
"""

from __future__ import annotations

import os
import sys
from typing import Dict, MutableMapping

ENV = "JAX_COMPILATION_CACHE_DIR"

_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"


def default_dir() -> str:
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, ".jax_cache")


def ensure_env(env: MutableMapping[str, str] = os.environ) -> str:
    """Default the variable in ``env`` where unset; returns the dir."""
    return env.setdefault(ENV, default_dir())


def apply() -> str:
    """``ensure_env`` for this process, plus the config update a
    process needs that imported jax before the variable was there."""
    path = ensure_env()
    if "jax" in sys.modules:
        import jax

        if jax.config.jax_compilation_cache_dir != path:
            jax.config.update("jax_compilation_cache_dir", path)
    return path


def watch() -> Dict[str, int]:
    """Count this process's persistent-cache hits and misses from here
    on (JAX's own monitoring events); the returned dict is live."""
    import jax.monitoring

    counts = {"hits": 0, "misses": 0}

    def on_event(event: str, **_kw) -> None:
        if event == _HIT:
            counts["hits"] += 1
        elif event == _MISS:
            counts["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    return counts
