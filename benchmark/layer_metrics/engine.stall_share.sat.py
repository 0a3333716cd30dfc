"""engine: the share of the window lost to runs far slower than their program's usual, from the deltas of stats()["runs"] by_ms / s_by_ms: per program the seconds in bins at least four times the bin of its median run, less that many runs of the median bin's mean; 0.0 in a clean window; the program and the bin of the slowest run in the info line. Printed by a --trace 1 run only, so the value is the TRACED window's, and 0.0 is not yet a clean window's reading everywhere: the floor by cell is in PERF.md section 5 (PR 51: 0.76-1.45% in every window of serve-gpt2-large-sat, traced or not, from two decode intervals of 64-256 ms: the whole machine stops for ~110 ms about twice in 30 s, every process at once; 0.9-1.1% in traced windows of OLMoE, LFM2 and Olmo-Hybrid whose untraced windows read 0.0); a run 2 to 8 times its usual can stay under the four-times bin."""
from benchmark.harness import phases, run_ledger


@phases.quiet
def read(ctx):
    return run_ledger.stall_share_pct(ctx)
