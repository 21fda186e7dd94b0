"""Hits over the first ``hit_rate_ops_per_node`` operations of the window,
counted by the benchmark from the answers ``execute()`` returns: a
function of the seed and the semantics, not of speed."""


def read(ctx):
    return ctx.hit_rate
