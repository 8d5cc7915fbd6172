"""Share of the traced batch's device time in the objectives of decoded
schedules (the ``objectives`` scope, the ``schedule_eval`` kernel
included), from the profiler trace and the program's stage map
(``lib/stages.py``)."""
import stages


def read(ctx):
    return stages.share(ctx, "objectives")
