"""ray_tpu.llm — LLM inference plane.

Continuous-batching generation engine (Orca-style iteration-level
scheduling) over a vLLM-style paged KV cache, served through
``ray_tpu.serve`` with token streaming, request autoscaling, and the
PR-8 resilience semantics.  See README "LLM serving"; the serving
cells of ``BENCHMARK.json`` (``benchmark/run.py``) measure it.

Tokens are chosen on the device (``sampling.sample_tokens``, a jitted
program the engine runs after every forward); ``sample`` and the other
numpy functions of ``sampling`` are its plain reference.
"""

from __future__ import annotations

from .engine import EngineConfig, GenerationEngine  # noqa: F401
from .sampling import SamplingParams, sample  # noqa: F401
from .serving import LLMDeployment, llm_deployment  # noqa: F401

__all__ = [
    "EngineConfig", "GenerationEngine", "LLMDeployment",
    "SamplingParams", "llm_deployment", "sample",
]
