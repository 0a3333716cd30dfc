"""delta-rule mixers: the recurrence alone: the least time the chip's memory could take to read and write once the float32 state of every slot a decode run moves (the step works by slot over a layer's whole slab: stats()["state"] slots_total x KDA layers x heads x d_k x d_v x 4 B x 2, benchmark/harness/kda_flops.py; the running rows alone, state_rows_updated, beside it in the info line; it is written by the step before, so it cannot be prefetched as a weight can) over the device time under kda.step, a kernel kda_step filed by its instruction's name, state-shaped asynchronous copies added."""
from benchmark.harness import kda_phases, phases


@phases.quiet
def read(ctx):
    r = kda_phases.step_roofline(ctx)
    return r["pct"] if r else None
