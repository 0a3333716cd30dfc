"""state-space mixers: the least time the chip's memory could take for what a decode run's mixers must move (stats()["state"]: state_rows_updated x state_row_bytes read and written once, plus mixer_weight_bytes a state-space layer: benchmark/harness/ssm_flops.py) over ssm.mixer_ms.sat."""
from benchmark.harness import phases, ssm_phases


@phases.quiet
def read(ctx):
    r = ssm_phases.mixer_roofline(ctx)
    return r["pct"] if r else None
