"""Share of the traced batch's device time in the carbon timing sweeps
(the ``timing_sweep`` scope, row selection included), from the profiler
trace and the program's stage map (``lib/stages.py``)."""
import stages


def read(ctx):
    return stages.share(ctx, "timing_sweep")
