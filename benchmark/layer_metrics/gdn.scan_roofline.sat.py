"""Gated DeltaNet mixers: over the capture's prefill runs, the least time the chip could take for what the delta rule's MATHEMATICS needs at each run's BUCKET's length whatever implements it (benchmark/harness/gdn_flops.py: the recurrent form's 7 d_k d_v FLOPs a token a head, and q, k, v and the output read or written once with one float32 of log-decay a head: 2 (2 d_k + 2 d_v) + 4 bytes; the chunked form's own matmuls and solve are not counted), summed, over gdn.scan_ms.sat's time, summed."""
from benchmark.harness import gdn_phases, phases


@phases.quiet
def read(ctx):
    r = gdn_phases.scan_roofline(ctx)
    return r["pct"] if r else None
