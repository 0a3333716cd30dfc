"""Gated DeltaNet mixers: device time per prefill run (a jit_fwd run that starts inside an llm.prefill annotation) of the operations under the scope gdn.scan (the chunked delta rule with a scalar decay: the chunk's decay matrix, the scores, the triangular solve, the state carried from chunk to chunk, its load and store), all linear_attention layers; by bucket in the info line."""
from benchmark.harness import gdn_phases, phases


@phases.quiet
def read(ctx):
    return (gdn_phases.capture(ctx) or {}).get("scan_ms")
