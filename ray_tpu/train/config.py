"""Train-stack configuration dataclasses.

Role-equivalent to the reference's air config surface (ref:
python/ray/air/config.py ScalingConfig/RunConfig/FailureConfig/
CheckpointConfig, python/ray/train/_checkpoint.py).  TPU-era default: a
worker is one TPU *host* (use_tpu implies chips-per-worker resources and
STRICT_SPREAD gang placement so worker == jax process).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Any, Dict, Optional


@dataclass
class ScalingConfig:
    num_workers: int = 1
    use_tpu: bool = False
    resources_per_worker: Optional[Dict[str, float]] = None
    placement_strategy: str = "PACK"
    # Environment applied to every gang worker BEFORE the backend
    # bootstrap hook runs (i.e. before the worker's first jax import)
    # — the supported way to set process-level XLA knobs like
    # XLA_FLAGS=--xla_force_host_platform_device_count=N for the CPU
    # multi-process CI mesh, or libtpu tuning flags in production.
    worker_env: Optional[Dict[str, str]] = None

    def worker_resources(self) -> Dict[str, float]:
        """``use_tpu`` with no explicit resources asks for one host's
        chips: ``RT_TPU_PER_WORKER`` when set, else what one TPU node
        of the running cluster has (a one-chip machine gives 1, a 2x2
        host 4).  A cluster with no TPU node fails here, at once."""
        if self.resources_per_worker:
            return dict(self.resources_per_worker)
        res: Dict[str, float] = {"CPU": 1.0}
        if self.use_tpu:
            env = os.environ.get("RT_TPU_PER_WORKER")
            res["TPU"] = float(env) if env else _chips_per_tpu_node()
        return res


def _chips_per_tpu_node() -> float:
    from ..util.chips import max_node_chips

    chips = max_node_chips()
    if chips <= 0:
        raise RuntimeError(
            "ScalingConfig(use_tpu=True): no node of this cluster has a "
            "TPU resource; start it on a TPU host, or pass "
            "resources_per_worker / RT_TPU_PER_WORKER")
    return chips


@dataclass
class FailureConfig:
    max_failures: int = 0


@dataclass
class TelemetryConfig:
    """Declared model-cost figures the telemetry plane needs to turn
    per-step reports into tokens/sec and achieved MFU gauges (the
    runtime cannot derive FLOPs-per-token from a closed jit).

    ``model_flops_per_token`` is the training cost (fwd+bwd) per token
    — e.g. ``GPT2Config.flops_per_token()``.  With it unset (0) the
    MFU gauge is simply not emitted; step-time and goodput metrics
    work regardless.
    """

    model_flops_per_token: float = 0.0
    tokens_per_step: float = 0.0       # per-worker tokens per report
    peak_flops_per_device: float = 0.0  # 0 = resolve from device_kind
    devices_per_worker: int = 1

    def resolved_peak_flops(self) -> float:
        if self.peak_flops_per_device > 0:
            return self.peak_flops_per_device
        # RT_PEAK_FLOPS_PER_DEVICE, else the chip table row of this
        # worker's own device; an unknown device raises.
        from ..util import xprof

        return xprof.resolve_peak_flops()


@dataclass
class CheckpointConfig:
    num_to_keep: Optional[int] = None
    checkpoint_score_attribute: Optional[str] = None
    checkpoint_score_order: str = "max"


@dataclass
class RunConfig:
    name: Optional[str] = None
    storage_path: Optional[str] = None
    failure_config: FailureConfig = field(default_factory=FailureConfig)
    checkpoint_config: CheckpointConfig = field(
        default_factory=CheckpointConfig)
    telemetry: TelemetryConfig = field(default_factory=TelemetryConfig)

    def resolved_storage_path(self) -> str:
        base = self.storage_path or os.path.expanduser("~/ray_tpu_results")
        name = self.name or "train_run"
        return os.path.join(base, name)


@dataclass
class Result:
    """What fit() returns (ref: python/ray/air/result.py)."""

    metrics: Dict[str, Any] = field(default_factory=dict)
    checkpoint: Optional[Any] = None
    path: str = ""
    error: Optional[BaseException] = None
    metrics_history: list = field(default_factory=list)
