"""Input generators of the benchmark: carbon traces, jobs, fleets, arrivals.

These are the yardstick's own copies of the generators the scheduler was
written against (the paper's Section 3.1 setup), kept here so that a change
to the program cannot change the traffic it is measured on.  Everything is
plain numpy and a pure function of the ``numpy.random.Generator`` passed in.

A job is ``(arrival, base_durations, edges)``: base durations in epochs on
a speed-1 machine, edges ``(u, v)`` with ``u < v`` (topological order).
"""
from __future__ import annotations

import math
import zlib

import numpy as np

EPOCH_HOURS = 0.25
EPOCHS_PER_HOUR = 4
EPOCHS_PER_DAY = 96

# The paper's heterogeneous server menu (Section 3.1): five classes whose
# power grows faster than their speed.
HETERO_POWERS_KW = (0.25, 0.5, 1.0, 1.5, 2.0)
HETERO_SPEEDS = (1.0 / 3.0, 1.0 / 2.0, 1.0, 4.0 / 3.0, 2.0)

# Statistical profile of each region's grid (mean level, diurnal swing,
# solar dip, hour-to-hour noise, seasonal swing, floor), gCO2/kWh.
REGIONS = {
    "AU-SA": (170.0, 110.0, 120.0, 45.0, 25.0, 5.0),
    "CAL": (240.0, 70.0, 140.0, 30.0, 30.0, 5.0),
    "TEX": (420.0, 55.0, 45.0, 25.0, 20.0, 5.0),
    "CA-ON": (45.0, 28.0, 10.0, 12.0, 8.0, 5.0),
}


def seed_rng(seed: int, *stream: int) -> np.random.Generator:
    """The generator for one named stream of a run's seed.  Any whole
    number is a seed: it is reduced into 64 bits first."""
    return np.random.default_rng([seed % (1 << 64), *stream])


# ---------------------------------------------------------------------------
# Carbon intensity.
# ---------------------------------------------------------------------------

def synthesize(region: str, days: int, seed: int) -> np.ndarray:
    """Synthetic float32 intensity per 15-minute epoch over ``days`` days:
    diurnal demand, a seasonal solar dip, a seasonal swing and AR(1)
    hour-to-hour noise, floored."""
    mean, diurnal_amp, solar_depth, noise_std, seasonal_amp, floor = \
        REGIONS[region]
    rng = np.random.default_rng(
        (seed % (1 << 63), zlib.crc32(region.encode()) & 0xFFFF))
    hours = days * 24
    t = np.arange(hours, dtype=np.float64)
    hod = t % 24.0
    doy = t / 24.0
    diurnal = diurnal_amp * np.sin((hod - 9.0) / 24.0 * 2 * np.pi)
    season = 1.0 + 0.35 * np.sin((doy - 15.0) / 366.0 * 2 * np.pi)
    solar = -solar_depth * season * np.exp(-0.5 * ((hod - 12.5) / 2.6) ** 2)
    seasonal = seasonal_amp * np.sin((doy - 30.0) / 366.0 * 2 * np.pi)
    eps = rng.normal(0.0, noise_std, size=hours)
    noise = np.empty(hours)
    acc = 0.0
    for i in range(hours):
        acc = 0.82 * acc + eps[i]
        noise[i] = acc
    noise *= np.sqrt(1 - 0.82 ** 2)
    hourly = np.maximum(floor, mean + diurnal + solar + seasonal + noise)
    return np.repeat(hourly, EPOCHS_PER_HOUR).astype(np.float32)


def window(intensity: np.ndarray, start: int, length: int) -> np.ndarray:
    """``length`` epochs from ``start``, wrapping round the trace."""
    idx = (start + np.arange(length)) % intensity.shape[0]
    return intensity[idx]


def cumulative_f32(intensity: np.ndarray) -> np.ndarray:
    """The scheduler's input form of a trace: ``cum[e]`` (gCO2 per kW),
    summed in float64 and stored as float32, length E+1."""
    cum = np.zeros(intensity.shape[0] + 1, dtype=np.float64)
    np.cumsum(intensity.astype(np.float64) * EPOCH_HOURS, out=cum[1:])
    return cum.astype(np.float32)


# ---------------------------------------------------------------------------
# Jobs.
# ---------------------------------------------------------------------------

def _chain(k):
    return tuple((i, i + 1) for i in range(k - 1))


def _branch(k):
    if k <= 2:
        return _chain(k)
    return ((0, 1), (0, 2)) + tuple((v - 2, v) for v in range(3, k))


def _fanout(k):
    return tuple((0, v) for v in range(1, k))


PAPER_SHAPES = (_chain, _branch, _fanout)      # Fig. 3, in that order


def paper_job(rng: np.random.Generator, k: int, mean_dur: float,
              arrival_horizon: int):
    """One job of the paper's instances: a Fig. 3 shape drawn uniformly,
    exp(``mean_dur``) durations rounded up to whole epochs, arrival uniform
    over the next ``arrival_horizon`` epochs."""
    shape = PAPER_SHAPES[rng.integers(len(PAPER_SHAPES))]
    durs = np.maximum(1, np.ceil(rng.exponential(mean_dur, size=k)))
    arrival = int(rng.integers(0, arrival_horizon))
    return arrival, tuple(int(d) for d in durs), shape(k)


def layered_dag(rng: np.random.Generator, width: int, depth: int):
    """Random layered DAG: ``depth`` layers of 1..``width`` tasks; each
    task below the first layer takes every task of the layer above as a
    parent with probability 1/2, and one at random if it drew none."""
    widths = [int(rng.integers(1, width + 1)) for _ in range(depth)]
    edges = []
    node = 0
    prev = []
    for w in widths:
        layer = list(range(node, node + w))
        for v in layer:
            if prev:
                parents = [u for u in prev if rng.random() < 0.5]
                if not parents:
                    parents = [prev[int(rng.integers(len(prev)))]]
                edges.extend((u, v) for u in parents)
        prev = layer
        node += w
    return node, tuple(sorted(edges))


def stream_job(rng: np.random.Generator, width: int, depth: int,
               mean_dur: float):
    """One streamed job: a layered DAG with exp(``mean_dur``) durations
    (arrival set by the arrival process)."""
    k, edges = layered_dag(rng, width, depth)
    durs = np.maximum(1, np.ceil(rng.exponential(mean_dur, size=k)))
    return tuple(int(d) for d in durs), edges


# ---------------------------------------------------------------------------
# Fleets.
# ---------------------------------------------------------------------------

def fleet(kind: str, n_machines: int):
    """``(powers_kw, speeds)``: ``homog`` is 1 kW at speed 1 throughout;
    ``tiered`` cycles the paper's five classes over the machines."""
    if kind == "homog":
        return (1.0,) * n_machines, (1.0,) * n_machines
    if kind == "tiered":
        n = len(HETERO_POWERS_KW)
        return (tuple(HETERO_POWERS_KW[i % n] for i in range(n_machines)),
                tuple(HETERO_SPEEDS[i % n] for i in range(n_machines)))
    raise ValueError(f"unknown fleet {kind!r}")


def durations(base: tuple, speeds: tuple) -> np.ndarray:
    """[k, M] whole epochs of each task on each machine."""
    return np.asarray([[max(1, int(math.ceil(d / s))) for s in speeds]
                       for d in base], dtype=np.int64)


# ---------------------------------------------------------------------------
# Arrivals: a fixed number of jobs per stream, in an order drawn afresh.
# ---------------------------------------------------------------------------

def arrivals(kind: str, rng: np.random.Generator, n_jobs: int, horizon: int,
             mean_burst: float = 4.0) -> np.ndarray:
    """Sorted int arrival epochs in ``[0, horizon)``, exactly ``n_jobs`` of
    them.

    ``poisson``: a Poisson process conditioned on its count, i.e. ``n_jobs``
    uniform times.  ``bursty``: a compound Poisson process conditioned on
    its count: geometric(mean ``mean_burst``) burst sizes drawn until they
    cover ``n_jobs`` (the last one cut), each burst arriving together at a
    uniform time.  Fixing the count keeps the work of a stream the same
    from seed to seed; the seed changes only where the jobs fall.
    """
    if kind == "poisson":
        times = rng.uniform(0.0, horizon, size=n_jobs)
    elif kind == "bursty":
        sizes = []
        while sum(sizes) < n_jobs:
            sizes.append(int(rng.geometric(1.0 / mean_burst)))
        sizes[-1] -= sum(sizes) - n_jobs
        centers = rng.uniform(0.0, horizon, size=len(sizes))
        times = np.repeat(centers, sizes)
    else:
        raise ValueError(f"unknown arrival process {kind!r}")
    return np.sort(np.floor(times)).astype(np.int64)
