"""K/V attention's prefill among its own rows: device time per prefill run (a jit_fwd run that starts inside an llm.prefill annotation) of the full_attention layers' attn.core less the rows' store (kv.store): the flash kernel flash_fwd, filed by its instruction's name, with the transposes around it; all K/V layers, the mean over the capture's prefill runs, by bucket in the info line (benchmark/harness/gdn_phases.py)."""
from benchmark.harness import gdn_phases, phases


@phases.quiet
def read(ctx):
    return (gdn_phases.capture(ctx) or {}).get("attend_ms")
