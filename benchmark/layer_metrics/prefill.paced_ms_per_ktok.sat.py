"""model step, serving: milliseconds the window's prefills cost the engine's loop per 1,000 bucket tokens they computed, from the deltas of stats()["runs"]: paced_s of every llm_prefill[bucket] over runs x bucket; by bucket in the info line. The same seed asks for the same lengths, so the mix of buckets is the same from run to run. Printed by a --trace 1 run only, so the value is the TRACED window's: an untraced window's to 0.1-1% in seven cells, 4.9% above it in serve-gpt2-large-sat, whose short prefills run at the host's pace (PERF.md section 5, PR 51)."""
from benchmark.harness import phases, run_ledger


@phases.quiet
def read(ctx):
    return run_ledger.prefill_paced_ms_per_ktok(ctx)
