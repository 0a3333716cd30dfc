"""model step, serving: the least time the chip's memory could take to read the K/V rows a decode run's attention read (stats()["attention"]: kv_rows_read x kv_row_bytes: whole pages up to each length, K and V, all layers: benchmark/harness/attend_phases.py) over decode.attend_ms.sat."""
from benchmark.harness import attend_phases, phases


@phases.quiet
def read(ctx):
    r = attend_phases.attend_roofline(ctx)
    return r["pct"] if r else None
