"""Device time per traced access round in the operations scoped
``ditto.account`` on the fullest chip, in us (``bench/stages.py``)."""

from bench import stages


def read(ctx):
    split = stages.round_stages_us(ctx)
    return None if split is None else split["account"]
