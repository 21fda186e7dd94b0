"""Host time per ``execute()`` call: the benchmark's ``bench.execute``
span less the device busy time inside it, on the fullest chip; the mean
over the traced calls, in ms."""


def read(ctx):
    s = ctx.traced
    if s is None or not s.devices:
        return None
    calls = [x for x in s.spans if x[0] == "bench.execute"]
    if not calls:
        return None
    t0, t1 = s.window()
    dev = max(s.devices, key=lambda d: s.busy(d, t0, t1))
    host = [(e - b) - s.busy(dev, b, e) for _, b, e in calls]
    return sum(host) / len(host) / 1e6
