"""model step, serving: what a decode step cost the engine's loop over the WHOLE window, from the deltas of stats()["runs"]["llm_decode"]: paced_s over the runs whose interval it holds (from the ids of the program before on the host to this program's); against decode.device_ms.sat it is what the host adds to a decode step. Printed by a --trace 1 run only, so the value is the TRACED window's: an untraced window's to 0.2 ms where the decode program leaves the host slack, +1.1 to +1.3 ms above it in serve-gpt2-large-sat, serve-kimi-k2.5-4k and serve-xing4.0-29b-a4b-4k (PERF.md section 5, PR 51): hold it against another traced window."""
from benchmark.harness import phases, run_ledger


@phases.quiet
def read(ctx):
    return run_ledger.decode_paced_ms(ctx)
