"""Share of the window's untraced streams spent in the engine's host loop
outside the timed tick and admission calls (eviction validation, packing,
bookkeeping): 1 - (sum of tick and admission times) / wall time."""


def read(ctx):
    sp = ctx["spans"]
    if not sp.get("window_s") or "tick_s" not in sp:
        return None
    timed = sum(sp["tick_s"]) + sum(sp["admission_s"])
    return 100.0 * (1.0 - timed / sp["window_s"])
