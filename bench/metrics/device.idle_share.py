"""The share of the traced window in which no operation ran on the
fullest chip, in %: 1 - (union of its operations' intervals) / window."""


def read(ctx):
    s = ctx.traced
    if s is None or not s.devices:
        return None
    t0, t1 = s.window()
    if t1 <= t0:
        return None
    busy = max(s.busy(d, t0, t1) for d in s.devices)
    return 100.0 * (1.0 - busy / (t1 - t0))
