"""Peak device memory in use on the fullest chip at the end of the run
(``memory_stats()["peak_bytes_in_use"]``), in GB (1e9 bytes)."""


def read(ctx):
    return ctx.peak_bytes / 1e9 if ctx.peak_bytes else None
