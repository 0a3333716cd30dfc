"""latent attention: the least time the chip could take for a decode run's absorbed attention (every position read costs 2 x heads x (2 latent + rope) FLOPs and ONE row of stats()["attention"]["kv_row_bytes"], padding included: benchmark/harness/mla_flops.py; the larger of the two terms at the chip's peaks) over decode.attend_ms.sat; the bound in the info line."""
from benchmark.harness import mla_phases, phases


@phases.quiet
def read(ctx):
    r = mla_phases.attend_roofline(ctx)
    return r["pct"] if r else None
