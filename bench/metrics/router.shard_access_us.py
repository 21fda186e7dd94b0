"""Device time per traced round in the cluster runner's operations under
any of ``access_group``'s five stage scopes (``ditto.probe``,
``.hit_update``, ``.evict``, ``.apply``, ``.account``) on the fullest
chip, their union, in us (``bench/router_stages.py``): one shard's access
round over its ``n_shards * q`` request positions."""

from bench import router_stages


def read(ctx):
    split = router_stages.round_us(ctx)
    return None if split is None else split["shard_access"]
