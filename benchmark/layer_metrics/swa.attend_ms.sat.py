"""window layers' decode attention: device ms a decode run of kv.attend under attn.window (the paged-decode kernel over the rings, three layers), mean over the capture's decode runs (benchmark/harness/swa_phases.py)."""
from benchmark.harness import phases, swa_phases


@phases.quiet
def read(ctx):
    cap = swa_phases.capture(ctx)
    return cap["attend_ms"] if cap else None
