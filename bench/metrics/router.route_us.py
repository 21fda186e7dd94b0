"""Device time per traced round in the cluster runner's operations scoped
``ditto.route`` (packing each source's requests per destination,
unpacking them at the shard, merging the answers onto their lanes) on
the fullest chip, in us (``bench/router_stages.py``)."""

from bench import router_stages


def read(ctx):
    split = router_stages.round_us(ctx)
    return None if split is None else split["route"]
