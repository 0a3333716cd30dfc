"""kernels (ops/flash_attention.py): the Pallas kernels' device time over the device's busy time, from the trace."""
from benchmark.harness import readers


def read(ctx):
    return readers.kernel_share_pct(ctx)
