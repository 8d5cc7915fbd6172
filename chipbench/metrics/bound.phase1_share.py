"""Share of the traced batch's device time in the bound's first phase, the
makespan search and its decode (the ``phase1`` scope), from the profiler
trace and the program's stage map (``lib/stages.py``)."""
import stages


def read(ctx):
    return stages.share(ctx, "phase1")
