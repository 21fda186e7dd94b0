"""The access round's share of its HBM roofline, in %: the least time the
bytes its rounds need (``bench/roofline.py``) take at the chip's peak
bandwidth, over the device time of the runner's program in those
rounds."""

from bench import roofline


def read(ctx):
    s = ctx.traced
    if s is None or not s.devices or ctx.peaks is None:
        return None
    ns = s.runner_ns(ctx.config["runner_modules"])
    if not ns:
        return None
    need = roofline.least_seconds(
        roofline.access_bytes(ctx.config, ctx.traced_counters), ctx.peaks)
    return 100.0 * need / (ns / 1e9)
