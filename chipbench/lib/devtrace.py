"""Reduce a profiler trace to device busy time, per-op time and idle gaps.

The run marks the traced span on the host with a ``TraceAnnotation``
named :data:`WINDOW`; that event's span is the window.  Device time is
read from the ``XLA Ops`` line of every TPU plane, where an op that runs
others (a ``while``, a ``conditional``) encloses their events: busy time
is the union of the op intervals inside the window (averaged over the
chips that ran any op), an op's own time excludes the ops it encloses,
and idle gaps are the rest of the window.  Each gap is named by what the
host was doing at its middle: the innermost host event around it.
"""
from __future__ import annotations

import collections
import glob
import os
import re

import numpy as np

WINDOW = "chipbench.window"
DEVICE_PLANE = re.compile(r"/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
TOP = 10
IDLE_HOST = "(host between calls)"


def short(name: str) -> str:
    """An op's instruction name: ``%fusion.12 = f32[..] fusion(..)`` ->
    ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")[:80]


def merge(intervals):
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy, lo, hi):
    """The parts of ``[lo, hi]`` that no busy interval covers."""
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def self_times(events):
    """Own time of each ``(start, end, name)`` event, children excluded;
    events sorted by start, a child lying inside its parent."""
    own = collections.defaultdict(lambda: [0.0, 0])
    stack = []        # [end, name, start, time covered by children]

    def close(item):
        end, name, start, covered = item
        rec = own[name]
        rec[0] += (end - start) - covered
        rec[1] += 1
        if stack:
            stack[-1][3] += end - start

    for s, e, name in events:
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        stack.append([e, name, s, 0.0])
    while stack:
        close(stack.pop())
    return own


def host_activity(frames, times) -> list[str]:
    """For each time, the name of the innermost host event around it (the
    shortest one), in one sweep over the events sorted by start."""
    frames = sorted(frames)
    out = {}
    active, i = [], 0
    for t in sorted(set(times)):
        while i < len(frames) and frames[i][0] <= t:
            active.append(frames[i])
            i += 1
        active = [f for f in active if f[1] >= t]
        best = min(active, key=lambda f: f[1] - f[0], default=None)
        out[t] = best[2][:80] if best else IDLE_HOST
    return [out[t] for t in times]


def reduce_events(device_lines, host_lines) -> dict:
    """``device_lines``: per chip, ``[(start_ns, dur_ns, name)]`` of its
    ops; ``host_lines``: per host thread, its events.  The window is the
    :data:`WINDOW` event found on a host thread."""
    win = next(((s, s + d) for events in host_lines
                for s, d, name in events if name == WINDOW), None)
    if win is None:
        raise ValueError(f"no {WINDOW!r} event on any host thread")
    lo, hi = win
    frames = [(s, s + d, n) for events in host_lines for s, d, n in events
              if s < hi and s + d > lo and n != WINDOW]
    per_op = collections.defaultdict(lambda: [0.0, 0])
    busy_total, chips = 0.0, 0
    idle = collections.Counter()
    for events in device_lines:
        # By start, and an enclosing op before the ops it encloses.
        inside = sorted(((max(s, lo), min(s + d, hi), short(n))
                         for s, d, n in events if s < hi and s + d > lo),
                        key=lambda ev: (ev[0], -ev[1]))
        if not inside:
            continue
        chips += 1
        for name, (sec, n) in self_times(inside).items():
            per_op[name][0] += sec * 1e-9
            per_op[name][1] += n
        starts = np.fromiter((s for s, _, _ in inside), np.float64)
        ends = np.maximum.accumulate(
            np.fromiter((e for _, e, _ in inside), np.float64))
        # A new busy stretch starts where an op begins after every earlier
        # op has ended.
        new = np.concatenate([[True], starts[1:] > ends[:-1]])
        first = np.flatnonzero(new)
        last = np.concatenate([first[1:] - 1, [len(starts) - 1]])
        busy = list(zip(starts[first], ends[last]))
        busy_total += sum(e - s for s, e in busy) * 1e-9
        holes = gaps(busy, lo, hi)
        names = host_activity(frames, [(s + e) / 2 for s, e in holes])
        for (s, e), name in zip(holes, names):
            idle[name] += (e - s) * 1e-9
    if chips == 0:
        raise ValueError("no device op ran inside the traced window")
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1][0])[:TOP]
    return {
        "window_s": float(hi - lo) * 1e-9,
        "busy_s": float(busy_total) / chips,
        "ops": {n: {"seconds": v[0] / chips, "count": v[1]}
                for n, v in per_op.items()},
        "device_ops": [[n, v[0] / chips] for n, v in top_ops],
        "idle_gaps": [[n, float(s) / chips]
                      for n, s in idle.most_common(TOP)],
    }


def read_xplane(path: str):
    """(device lines, host lines) of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device, host = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.search(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device.append([(ev.start_ns, ev.duration_ns, ev.name)
                                   for ev in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.append([(ev.start_ns, ev.duration_ns, ev.name)
                             for ev in line.events])
    return device, host


def reduce(logdir: str) -> dict:
    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return reduce_events(*read_xplane(files[-1]))
