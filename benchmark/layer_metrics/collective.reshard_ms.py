"""collectives (parallel/, ICI): device time per traced step of the operations whose opcode is all-to-all or collective-permute (its -start and -done too): what a reshard the partitioner put in costs; by opcode in the info line; 0.0 where a traced step holds none."""
from collections import defaultdict

from benchmark.harness import phases, readers, trace as T

RESHARDS = ("all-to-all", "collective-permute")


@phases.quiet
def read(ctx):
    tr = phases.again(ctx)
    n = readers.traced_steps(ctx) if ctx.get("train") else 0
    if not tr or not tr.devices or not n:
        return None
    lo, hi = T.window_of(tr)
    per = 1e3 / 1e9 / len(tr.devices) / n     # ns -> ms a step a chip
    by_opcode = defaultdict(float)
    for dev in tr.devices:
        for name, s, e in T._leaves(dev, lo, hi):
            code = T.opcode(name)
            if code.startswith(RESHARDS):
                by_opcode[code] += (e - s) * per
    phases.note(ctx, "reshard_ms_by_opcode", dict(by_opcode))
    return sum(by_opcode.values())
