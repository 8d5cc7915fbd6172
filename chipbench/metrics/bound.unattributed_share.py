"""Share of the traced batch's device time under no stage of the bound:
copies and loops the compiler adds outside every scope, and code between
the phases.  It rises where a refactor drops a scope.  From the profiler
trace and the program's stage map (``lib/stages.py``)."""
import stages


def read(ctx):
    return stages.share(ctx, stages.UNATTRIBUTED)
