"""Starting and stopping the cluster a run measures, and what every runner
needs around it: the environment the workers inherit, the chips the host
shows, the processes a session leaves behind."""

from __future__ import annotations

import os
import sys
import tempfile
import time
from typing import Any, Dict, List

from .manifest import ROOT

CACHE_DIR = os.path.join(ROOT, ".jax_cache")       # fixed: part of the key
OUT_ROOT = os.path.join(ROOT, ".bench_out")
PLATFORM = "tpu"        # what the worker or the replica has to find


class BenchFailure(RuntimeError):
    """A run did not meet its conditions; no result line is printed."""


def prepare_environment() -> None:
    """Before the runtime is imported: the compile cache inside the
    checkout, and the checkout on the path of every worker (the train loop
    and the per-layer code live under benchmark/ and are imported there by
    name)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    parts = [ROOT] + [p for p in os.environ.get("PYTHONPATH", "").split(
        os.pathsep) if p and p != ROOT]
    os.environ["PYTHONPATH"] = os.pathsep.join(parts)


def chips_on_host() -> int:
    from ray_tpu.core.resources import detect_tpu

    info = detect_tpu()
    return int(info.num_chips) if info else 0


def session_root() -> str:
    return os.path.join(tempfile.gettempdir(), "ray_tpu")


def start():
    import ray_tpu

    return ray_tpu.init(
        mode="cluster",
        config={"object_store_backend": "pool",
                "session_dir_root": session_root()})


def session_processes(session: str) -> List[Dict[str, Any]]:
    """Live processes of the session (its name is in the command line of the
    controller and the agent, and in the workers' environment)."""
    found = []
    for pid in os.listdir("/proc"):
        if not pid.isdigit() or int(pid) == os.getpid():
            continue
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmd = f.read().replace(b"\0", b" ").decode(errors="replace")
            with open(f"/proc/{pid}/environ", "rb") as f:
                env = f.read().decode(errors="replace")
            with open(f"/proc/{pid}/stat") as f:
                zombie = f.read().rsplit(")", 1)[1].split()[0] == "Z"
        except OSError:
            continue
        if not zombie and (session in cmd
                           or f"RT_SESSION_NAME={session}\0" in env):
            found.append({"pid": int(pid), "cmd": cmd.strip()[:120]})
    return found


def stop(session: str, wait_s: float = 30.0) -> List[Dict[str, Any]]:
    """Shut the cluster down and wait until its processes have ended;
    returns those that have not."""
    import ray_tpu

    ray_tpu.shutdown()
    deadline = time.time() + wait_s
    left = session_processes(session)
    while left and time.time() < deadline:
        time.sleep(0.25)
        left = session_processes(session)
    return left


def keep_session_logs(session: str, dest: str) -> None:
    """After a failure: the end of every log of the session."""
    logs = os.path.join(session_root(), session, "logs")
    os.makedirs(dest, exist_ok=True)
    for name in sorted(os.listdir(logs) if os.path.isdir(logs) else []):
        try:
            with open(os.path.join(logs, name), "rb") as f:
                f.seek(0, os.SEEK_END)
                f.seek(max(f.tell() - 65536, 0))
                tail = f.read()
            with open(os.path.join(dest, name), "wb") as f:
                f.write(tail)
        except OSError:
            continue


def log(msg: str) -> None:
    """Progress goes to stderr: stdout's last line is the result."""
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)
