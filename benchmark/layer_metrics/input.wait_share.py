"""input (data/, iter_device_batches): share of the loop's wall time spent in next(batches); benchmark span in the train loop."""
from benchmark.harness import readers


def read(ctx):
    return readers.input_wait_share_pct(ctx)
