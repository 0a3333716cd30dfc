"""delta-rule mixers: over the capture's prefill runs, the least time the chip could take for what the delta rule's MATHEMATICS needs at each run's BUCKET's length whatever implements it (benchmark/harness/kda_flops.py: the recurrent form's 7 d_k d_v FLOPs a token a head, and q, k, v, the log-decay and the output read or written once; the chunked form's own matmuls and solve are not counted), summed, over kda.scan_ms.sat's time, summed."""
from benchmark.harness import kda_phases, phases


@phases.quiet
def read(ctx):
    r = kda_phases.scan_roofline(ctx)
    return r["pct"] if r else None
