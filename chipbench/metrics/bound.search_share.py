"""Share of the traced batch's device time in the SA search around the
decodes: init, RNG, proposals, acceptance, best tracking and migration
(the ``search`` scope, less the stages inside it), from the profiler
trace and the program's stage map (``lib/stages.py``)."""
import stages


def read(ctx):
    return stages.share(ctx, "search")
