"""The busiest shard's operations (gets + sets, from the program's
per-shard counters read after the window, counted from the hand-off to
the cell's lanes) over the shards' mean: the skew the router's owners
see.  The system sets it on the run's outcome; None where it does not."""


def read(ctx):
    return getattr(ctx, "load_imbalance", None)
