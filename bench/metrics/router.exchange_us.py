"""Device time per traced round in the cluster runner's operations scoped
``ditto.exchange`` (the router's ``all_to_all`` collectives) on the
fullest chip, in us (``bench/router_stages.py``)."""

from bench import router_stages


def read(ctx):
    split = router_stages.round_us(ctx)
    return None if split is None else split["exchange"]
