"""99th percentile of all requests' latency in the window, in ms.

A request's latency runs from its call's dispatch to its call's answers
being on the host; the requests of one call share it, and every call
carries the same number of requests, so the percentile over requests is
the percentile over calls (numpy's linear interpolation)."""

import numpy as np


def read(ctx):
    if not ctx.latencies_s:
        return None
    return float(np.percentile(np.asarray(ctx.latencies_s), 99)) * 1e3
