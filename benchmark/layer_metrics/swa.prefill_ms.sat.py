"""window layers' prefill attention: device ms a prefill run of the operations under the scope attn.window (the flash kernel under the band 0 <= i - j < window and what surrounds it, three layers; kv.store filed apart), mean over the capture's prefill runs (benchmark/harness/swa_phases.py)."""
from benchmark.harness import phases, swa_phases


@phases.quiet
def read(ctx):
    cap = swa_phases.capture(ctx)
    return cap["window_ms"] if cap else None
