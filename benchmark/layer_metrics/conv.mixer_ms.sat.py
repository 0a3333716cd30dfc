"""short-conv mixers: device time per decode run (a jit_fwd run that starts inside an llm.decode annotation) of the operations under the scopes conv.in_proj, conv.gate (both gates), conv.window (the window's read, the three taps, its write) and conv.out_proj, all short-conv layers; each scope's share in the info line, and beside it the bytes the mixers must move (benchmark/harness/conv_flops.py) with their least time at the chip's bandwidth: most of them arrive by asynchronous copies under other layers' operations, outside these scopes, so this time is held against no roofline (conv_phases.py has the readings)."""
from benchmark.harness import conv_phases, phases


@phases.quiet
def read(ctx):
    conv_phases.mixer_floor(ctx)
    return (conv_phases.capture(ctx) or {}).get("mixer_ms")
