"""Device idle time inside the program's ``ditto.execute.wait`` spans on
the fullest chip, per traced call, in ms: the host blocked on results
while the chip ran nothing (runtime-side latency)."""

from bench import stages


def read(ctx):
    s = ctx.traced
    if s is None or not s.devices:
        return None
    waits = stages.phase_spans(s, "wait")
    if not waits:
        return None
    dev = stages.fullest(s)
    idle = sum((e - b) - s.busy(dev, b, e) for _, b, e in waits)
    return idle / len(stages.calls(s)) / 1e6
