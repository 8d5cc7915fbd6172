"""Median wall time of the pool tick (``_pool_tick``), from the engine's own
synced timer (``tick_wall_s_*``), over the window's untraced streams."""
import numpy as np


def read(ctx):
    s = ctx["spans"].get("tick_s")
    return 1e3 * float(np.median(s)) if s else None
