"""Evictions per operation in the window, from the program's counters:
how much of the window's work is sampled eviction."""


def read(ctx):
    c = ctx.counters
    ops = c["gets"] + c["sets"]
    return c["evictions"] / ops if ops else None
