"""The replica class of a --trace 1 serving run: the program's
``LLMDeployment`` with ONE method more, a profiler capture of a few
seconds with the Python tracer off.

Why not ``ray_tpu.util.state.jax_profile``: its capture runs
``jax.profiler.start_trace`` with the default options, which trace every
Python call of the engine's host loop while it is being measured, and the
runtime offers no way to pass options.  So the capture is the benchmark's
own code in the replica, as the train loop is in the worker.
``llm_deployment`` still builds the application (options, lease,
max_ongoing_requests); only the class is swapped, through
``Deployment.options``.  A --trace 0 run uses the program's class
untouched.
"""

from __future__ import annotations

import time

from ray_tpu.llm.serving import LLMDeployment


class TracedLLMDeployment(LLMDeployment):
    def bench_trace(self, log_dir: str, seconds: float) -> str:
        import jax

        self._engine_or_raise()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=options)
        try:
            time.sleep(seconds)
        finally:
            jax.profiler.stop_trace()
        return log_dir
