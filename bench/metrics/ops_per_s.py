"""Operations completed in the window over the window's wall time: every
call's requests, answered, counted by the benchmark."""


def read(ctx):
    return ctx.completed / ctx.window_s if ctx.window_s > 0 else None
