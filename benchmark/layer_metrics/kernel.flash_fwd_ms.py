"""kernels: device time per traced step of the Pallas kernel named flash_fwd (its HLO instruction's name), averaged over the chips."""
from benchmark.harness import phases


@phases.quiet
def read(ctx):
    return phases.kernel_ms(ctx, "flash_fwd")
