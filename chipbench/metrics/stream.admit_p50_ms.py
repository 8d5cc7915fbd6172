"""Median wall time of an admission solve (``_admission_eval`` and its
host sync), from the engine's own timer (``admission_wall_s_*``), over the
window's untraced streams."""
import numpy as np


def read(ctx):
    s = ctx["spans"].get("admission_s")
    return 1e3 * float(np.median(s)) if s else None
