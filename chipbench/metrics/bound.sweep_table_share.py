"""Share of the traced batch's device time in building the per-instance
start-cost tables (the ``sweep_table`` scope), from the profiler trace and
the program's stage map (``lib/stages.py``)."""
import stages


def read(ctx):
    return stages.share(ctx, "sweep_table")
