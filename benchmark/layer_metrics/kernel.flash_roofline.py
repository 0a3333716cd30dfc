"""kernels: the least time the chip could take for causal attention forward and backward (FLOPs and bytes from shapes, benchmark/harness/flops.py) over the kernels' time in the trace."""
from benchmark.harness import readers


def read(ctx):
    return readers.flash_roofline_pct(ctx)
