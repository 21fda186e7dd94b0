"""Mean host time per traced ``execute()`` call in its ``fetch`` phase:
the program's ``ditto.execute.fetch`` spans (``bench/stages.py``), in ms."""

from bench import stages


def read(ctx):
    return stages.phase_ms_per_call(ctx.traced, "fetch")
