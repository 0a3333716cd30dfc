"""window layers' decode attention: the least time the chip's memory could take to read the ring rows a decode run's window layers read (stats()["attention"]: window_rows_read x kv_row_bytes: each running row's min(n_cached + 1, window) positions in whole pages, K and V, three layers) over swa.attend_ms.sat."""
from benchmark.harness import phases, swa_phases


@phases.quiet
def read(ctx):
    r = swa_phases.attend_roofline(ctx)
    return r["pct"] if r else None
