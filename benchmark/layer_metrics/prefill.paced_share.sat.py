"""model step, serving: the share of the window that the prefill programs cost the engine's loop, from the deltas of stats()["runs"]: paced_s of every llm_prefill[bucket] over counted_s; runs, tokens and seconds by bucket in the info line (run_ledger). Printed by a --trace 1 run only, so the value is the TRACED window's: an untraced window's to 0.3 points in five cells, 2.0 | 4.1 | 2.7 points UNDER it in serve-gpt2-large-sat | serve-kimi-k2.5-4k | serve-xing4.0-29b-a4b-4k, where the traced window's host-bound decode steps run longer (PERF.md section 5, PR 51): hold it against another traced window."""
from benchmark.harness import phases, run_ledger


@phases.quiet
def read(ctx):
    return run_ledger.prefill_paced_share_pct(ctx)
