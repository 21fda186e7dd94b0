"""Device time per traced round in the cluster runner's operations scoped
``ditto.sync`` (the cross-shard weight sync) on the fullest chip, in us
(``bench/router_stages.py``)."""

from bench import router_stages


def read(ctx):
    split = router_stages.round_us(ctx)
    return None if split is None else split["sync"]
