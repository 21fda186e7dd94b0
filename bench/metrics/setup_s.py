"""Process start to the first timed call: imports, traffic, the pool's
fill, the warm-up calls and, in a run that compiles, compilation."""


def read(ctx):
    return ctx.setup_s
