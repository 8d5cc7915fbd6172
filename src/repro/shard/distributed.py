"""Multi-process runtime: ``jax.distributed`` wiring for the shard layer.

PR 5's sharding is single-process over N local devices; this module is the
step it was designed for — the same instance-axis programs spanning a
**process-spanning** device mesh, so the structure sweep and the learner
run across real worker processes (and, on a cluster, real hosts).  It owns
exactly three things:

* :func:`initialize` — a thin, idempotent wrapper over
  ``jax.distributed.initialize`` taking the coordinator address / process
  id / process count from arguments or from the ``REPRO_COORDINATOR`` /
  ``REPRO_NUM_PROCESSES`` / ``REPRO_PROCESS_ID`` environment (the contract
  ``tests/harness.py`` spawns workers with).  On the CPU backend it
  selects the ``gloo`` cross-process collectives implementation first —
  XLA's default CPU collectives cannot run multi-process computations at
  all, and the flag must be set before the backend initializes.
* :func:`initialize_from_env` — the no-op-when-unset variant benchmarks
  call unconditionally: a plain single-process run sees no env and pays
  nothing.
* :func:`mesh_devices` — the canonical device order for a process-spanning
  mesh: ``devices_per_process`` devices from every process, **process-major**
  (process 0's devices first), so the ``"inst"`` mesh axis maps rows to
  contiguous blocks in process-id order — the canonical row order every
  cross-process ``all_gather`` in :mod:`repro.shard` reassembles.

The bit-exactness story does not change here: collectives only *move*
rows (``all_gather`` into canonical order), never reduce them — reductions
stay the explicitly-sequenced ``seq_sum`` of :mod:`repro.learn.train` —
so sharded == single-device bit-for-bit at any (process count, device
count), goldens unchanged (``tests/test_distributed.py``).
"""
from __future__ import annotations

import os

import jax

ENV_COORDINATOR = "REPRO_COORDINATOR"
ENV_NUM_PROCESSES = "REPRO_NUM_PROCESSES"
ENV_PROCESS_ID = "REPRO_PROCESS_ID"

_INITIALIZED = False


def is_initialized() -> bool:
    """True once :func:`initialize` has run in this process."""
    return _INITIALIZED


def _enable_cpu_collectives() -> None:
    """Select gloo for cross-process CPU collectives (the XLA default CPU
    collectives raise ``Multiprocess computations aren't implemented on
    the CPU backend``).  Must run before the backend is created; other
    backends ignore the option."""
    jax.config.update("jax_cpu_collectives_implementation", "gloo")


def initialize(coordinator: str | None = None,
               num_processes: int | None = None,
               process_id: int | None = None,
               initialization_timeout: int | None = None) -> None:
    """``jax.distributed.initialize`` from args or the ``REPRO_*`` env.

    Arguments win over the environment; either source must provide all
    three of (coordinator address, process count, process id).  Idempotent
    — a second call in the same process is a no-op, so library code and
    entry points can both call it.  ``initialization_timeout`` (seconds)
    bounds the coordination barrier — a dead worker then fails loudly
    instead of hanging the fleet for the default 300 s.
    """
    global _INITIALIZED
    if _INITIALIZED:
        return
    coordinator = coordinator or os.environ.get(ENV_COORDINATOR)
    if num_processes is None and os.environ.get(ENV_NUM_PROCESSES):
        num_processes = int(os.environ[ENV_NUM_PROCESSES])
    if process_id is None and os.environ.get(ENV_PROCESS_ID):
        process_id = int(os.environ[ENV_PROCESS_ID])
    if coordinator is None or num_processes is None or process_id is None:
        raise ValueError(
            "distributed.initialize needs coordinator address, process "
            "count and process id — pass them or set "
            f"{ENV_COORDINATOR}/{ENV_NUM_PROCESSES}/{ENV_PROCESS_ID} "
            f"(got coordinator={coordinator!r}, "
            f"num_processes={num_processes!r}, process_id={process_id!r})")
    _enable_cpu_collectives()
    kw = {}
    if initialization_timeout is not None:
        kw["initialization_timeout"] = int(initialization_timeout)
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=int(num_processes),
                               process_id=int(process_id), **kw)
    _INITIALIZED = True


def initialize_from_env(initialization_timeout: int | None = None) -> bool:
    """Initialize iff the ``REPRO_*`` env is set; returns whether it is.

    The benchmark entry points call this unconditionally: a plain
    single-process invocation (no env) is untouched, while the same
    command line spawned by ``tests/harness.py`` (or
    ``python -m tests.harness``) joins the process fleet.
    """
    if not os.environ.get(ENV_COORDINATOR):
        return False
    initialize(initialization_timeout=initialization_timeout)
    return True


def process_count() -> int:
    return jax.process_count()


def process_index() -> int:
    return jax.process_index()


def mesh_devices(devices_per_process: int | None = None,
                 process_order: tuple[int, ...] | None = None) -> list:
    """Process-major device list for a process-spanning ``"inst"`` mesh.

    Takes the first ``devices_per_process`` local devices of every process
    (default: every process's full complement, which must agree across
    processes) in ``process_order`` (default ``0..P-1``).  Process-major
    order is the canonical layout: mesh position — and therefore the row
    block a device owns — is a pure function of (process id, local device
    ordinal), independent of which OS process got spawned first
    (the process-permutation invariance ``tests/test_distributed.py``
    locks is exactly that ``process_order`` never changes a number).
    """
    procs = jax.process_count()
    order = tuple(range(procs)) if process_order is None else \
        tuple(int(p) for p in process_order)
    if sorted(order) != list(range(procs)):
        raise ValueError(f"process_order {order} is not a permutation of "
                         f"0..{procs - 1}")
    by_proc: dict[int, list] = {p: [] for p in range(procs)}
    for d in jax.devices():
        by_proc[d.process_index].append(d)
    per = (min(len(v) for v in by_proc.values())
           if devices_per_process is None else int(devices_per_process))
    if per < 1:
        raise ValueError(f"mesh_devices: need >= 1 device per process, "
                         f"got {per}")
    for p, devs in by_proc.items():
        if len(devs) < per:
            raise ValueError(
                f"mesh_devices: process {p} exposes {len(devs)} device(s), "
                f"{per} per process requested — on CPU, force fake devices "
                "in every worker: XLA_FLAGS="
                f"--xla_force_host_platform_device_count={per}")
    return [d for p in order for d in by_proc[p][:per]]
