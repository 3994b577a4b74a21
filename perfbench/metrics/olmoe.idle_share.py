"""olmoe.idle_share: share of the scoring window in which no op ran on the
chip."""
from perfbench import readers


def read(ctx):
    return readers.idle_share(ctx)
