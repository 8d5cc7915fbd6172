"""Share of the traced batch's device time in the bound's serial placement
scan (the ``sgs`` scope), from the profiler trace and the program's stage
map (``lib/stages.py``)."""
import stages


def read(ctx):
    return stages.share(ctx, "sgs")
