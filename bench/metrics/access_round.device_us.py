"""Device time of the trace runner's program (the configuration's
``runner_modules``) per access round in the traced calls, on the fullest
chip, in us.  A trace with no module of that name is an error
(``Summary.runner_ns``)."""


def read(ctx):
    s = ctx.traced
    if s is None or not s.devices or not ctx.traced_rounds:
        return None
    ns = s.runner_ns(ctx.config["runner_modules"])
    return ns / 1e3 / ctx.traced_rounds if ns else None
