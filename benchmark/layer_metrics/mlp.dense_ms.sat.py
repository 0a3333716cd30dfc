"""experts: device time per decode run of the operations under the scope mlp.dense (the dense SwiGLU of the leading layers, which every token passes through), both dense layers. Two dense layers in this cell's ten are four times the model's share (two in forty)."""
from benchmark.harness import conv_phases, phases


@phases.quiet
def read(ctx):
    return (conv_phases.capture(ctx) or {}).get("dense_ms")
