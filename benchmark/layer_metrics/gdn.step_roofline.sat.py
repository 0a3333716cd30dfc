"""Gated DeltaNet mixers: the recurrence alone: the least time the chip's memory could take to read and write once the float32 state of every row a decode run updated (stats()[state] state_rows_updated x heads x d_k x d_v x 4 B x 2 on the UNPADDED 96 x 192 state, benchmark/harness/gdn_flops.py: a layout that pads it reads as a loss; the state is written by the step before, so it cannot be prefetched as a weight can) over the device time of the step kernel kda_step, filed by its instruction's name under gdn.step."""
from benchmark.harness import gdn_phases, phases


@phases.quiet
def read(ctx):
    r = gdn_phases.step_roofline(ctx)
    return r["pct"] if r else None
