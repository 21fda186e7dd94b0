"""Device time per traced access round of the runner's program outside
every ``ditto.*`` stage scope, in us: the scan's own time and the code
around the access round (``bench/stages.py``).  With the five stage
metrics it sums to ``access_round.device_us``."""

from bench import stages


def read(ctx):
    split = stages.round_stages_us(ctx)
    return None if split is None else split["outside"]
