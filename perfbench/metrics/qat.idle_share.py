"""qat.idle_share: share of the QAT window in which no op ran on the chip."""
from perfbench import readers


def read(ctx):
    return readers.idle_share(ctx)
