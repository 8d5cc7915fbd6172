"""Subprocess harness: forced-fake-device and multi-process jax test runs.

JAX locks its device count (and its process topology) at first backend
init, so any test that needs "8 CPU devices" or "2 processes x 4 devices"
inside a plain tier-1 run must spawn fresh interpreters.  This module is
the one spawn path both kinds of test share:

* :func:`run_forced_devices` — the single-subprocess pattern
  ``tests/test_shard.py`` / ``tests/test_multidevice.py`` use: run a
  payload under ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
  and parse the payload's last stdout line as JSON (the stdout protocol —
  payloads may log freely as long as the final line is the result).
* :func:`run_distributed` — the multi-process pattern
  ``tests/test_distributed.py`` uses: pick a free coordinator port, spawn
  one worker per rank with the ``REPRO_COORDINATOR`` /
  ``REPRO_NUM_PROCESSES`` / ``REPRO_PROCESS_ID`` env contract
  (:mod:`repro.shard.distributed` reads it via ``initialize_from_env``),
  each with ``devices`` forced fake CPU devices, collect every rank's
  stdout-protocol result, **assert the ranks agree bit-for-bit**, and
  report which rank hung when the fleet times out.
* ``python -m tests.harness --processes P --devices D -- cmd ...`` — the
  same spawn path as a CLI, for running e.g.
  ``benchmarks/structure_sweep.py --tiny --processes 2 --devices 4``
  multi-process locally or in CI.

Every worker is a fleet of fake CPU devices, so it is pinned to the CPU
(``JAX_PLATFORMS=cpu``): on a host with an accelerator, a child that
reached for it would contend with the parent, which holds it.  Workers are
spawned with ``PYTHONPATH`` covering ``src`` and the repo root, and with
any inherited ``REPRO_*`` contract scrubbed first so a nested
single-process payload never accidentally joins an outer fleet.
"""
from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SRC = os.path.join(REPO_ROOT, "src")

ENV_COORDINATOR = "REPRO_COORDINATOR"
ENV_NUM_PROCESSES = "REPRO_NUM_PROCESSES"
ENV_PROCESS_ID = "REPRO_PROCESS_ID"

# Payload prelude: join the fleet described by the env (no-op when the
# harness spawned a plain single-process payload).  The short timeout is
# what turns a dead worker into a loud failure instead of a 300 s hang.
DISTRIBUTED_PRELUDE = (
    "from repro.shard.distributed import initialize_from_env\n"
    "initialize_from_env(initialization_timeout=120)\n")


def _worker_env(devices: int, extra: dict | None = None) -> dict:
    env = dict(os.environ)
    path = [SRC, REPO_ROOT]
    if env.get("PYTHONPATH"):
        path.append(env["PYTHONPATH"])
    env["PYTHONPATH"] = os.pathsep.join(path)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    for k in (ENV_COORDINATOR, ENV_NUM_PROCESSES, ENV_PROCESS_ID):
        env.pop(k, None)
    if extra:
        env.update(extra)
    return env


def _last_json_line(stdout: str, ctx: str):
    lines = stdout.strip().splitlines()
    assert lines, f"{ctx}: payload produced no stdout"
    try:
        return json.loads(lines[-1])
    except json.JSONDecodeError as e:
        raise AssertionError(
            f"{ctx}: last stdout line is not JSON ({e}): {lines[-1]!r}")


def free_port() -> int:
    """A free localhost TCP port for the coordinator."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def run_forced_devices(payload: str, devices: int = 8,
                       timeout: int = 900):
    """Run ``payload`` in one subprocess with ``devices`` forced fake CPU
    devices; returns the JSON parsed from its last stdout line."""
    out = subprocess.run([sys.executable, "-c", payload],
                         env=_worker_env(devices), capture_output=True,
                         text=True, timeout=timeout)
    assert out.returncode == 0, (
        f"forced-{devices}-device payload failed "
        f"(rc={out.returncode}):\n{out.stderr[-2000:]}")
    return _last_json_line(out.stdout, f"forced-{devices}-device payload")


def run_distributed(payload: str, processes: int, devices: int,
                    timeout: int = 900,
                    spawn_order: tuple[int, ...] | None = None) -> dict:
    """Run ``payload`` on a ``processes``-rank fleet, ``devices`` fake CPU
    devices per rank.

    Every rank gets the ``REPRO_*`` env contract (the payload joins via
    ``initialize_from_env`` — prepend :data:`DISTRIBUTED_PRELUDE`);
    ``spawn_order`` permutes the order the OS processes are launched in
    (rank identity comes from the env, so results must not change).

    Collects each rank's stdout-protocol result, asserts every rank
    produced the **identical** JSON (the cross-process agreement the
    replicated-output contract promises), and returns ``{rank: result}``.
    Raises :class:`TimeoutError` naming the rank(s) still running when
    the deadline passes — the dead-worker failure mode.
    """
    order = (tuple(range(processes)) if spawn_order is None
             else tuple(spawn_order))
    assert sorted(order) == list(range(processes)), order
    coord = f"127.0.0.1:{free_port()}"
    procs: dict[int, subprocess.Popen] = {}
    try:
        for rank in order:
            procs[rank] = subprocess.Popen(
                [sys.executable, "-c", payload],
                env=_worker_env(devices, {
                    ENV_COORDINATOR: coord,
                    ENV_NUM_PROCESSES: str(processes),
                    ENV_PROCESS_ID: str(rank),
                }),
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        deadline = time.monotonic() + timeout
        while (time.monotonic() < deadline
               and any(p.poll() is None for p in procs.values())):
            time.sleep(0.2)
        hung = sorted(r for r, p in procs.items() if p.poll() is None)
        if hung:
            done = sorted(r for r in procs if r not in hung)
            raise TimeoutError(
                f"distributed run ({processes} proc x {devices} dev) timed "
                f"out after {timeout}s: rank(s) {hung} still running, "
                f"rank(s) {done} exited — a worker likely died before the "
                "coordination barrier or the payload deadlocked")
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
    outs = {r: p.communicate() for r, p in procs.items()}
    bad = {r: p.returncode for r, p in procs.items() if p.returncode != 0}
    assert not bad, (
        f"rank(s) {sorted(bad)} failed (rc={bad}):\n" + "\n".join(
            f"--- rank {r} stderr ---\n{outs[r][1][-2000:]}"
            for r in sorted(bad)))
    results = {r: _last_json_line(out, f"rank {r}")
               for r, (out, _err) in outs.items()}
    first = results[min(results)]
    for r in sorted(results):
        assert results[r] == first, (
            f"cross-process disagreement: rank {r} != rank {min(results)}\n"
            f"rank {min(results)}: {first}\nrank {r}: {results[r]}")
    return results


def launch(cmd: list[str], processes: int, devices: int,
           timeout: int = 3600) -> int:
    """CLI spawn path: run ``cmd`` once per rank under the ``REPRO_*``
    contract.  Rank 0 inherits this terminal; other ranks log to
    ``harness-rank<N>.log`` in the cwd.  Returns the max exit code."""
    coord = f"127.0.0.1:{free_port()}"
    procs, logs = {}, {}
    for rank in range(processes):
        if rank == 0:
            out = err = None
        else:
            logs[rank] = f"harness-rank{rank}.log"
            out = err = open(logs[rank], "w")
        procs[rank] = subprocess.Popen(
            cmd, env=_worker_env(devices, {
                ENV_COORDINATOR: coord,
                ENV_NUM_PROCESSES: str(processes),
                ENV_PROCESS_ID: str(rank),
            }), stdout=out, stderr=err)
    deadline = time.monotonic() + timeout
    while (time.monotonic() < deadline
           and any(p.poll() is None for p in procs.values())):
        time.sleep(0.5)
    hung = sorted(r for r, p in procs.items() if p.poll() is None)
    for p in procs.values():
        if p.poll() is None:
            p.kill()
    rcs = {r: p.wait() for r, p in procs.items()}
    if hung:
        print(f"harness: rank(s) {hung} timed out after {timeout}s and "
              "were killed", file=sys.stderr)
    for r, path in logs.items():
        if rcs[r] != 0:
            print(f"harness: rank {r} failed (rc={rcs[r]}), log: {path}",
                  file=sys.stderr)
    return max(max(rcs.values()), 1 if hung else 0)


def _main(argv: list[str]) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m tests.harness",
        description="Run a command once per rank on a local multi-process "
                    "jax fleet (CPU, fake devices per rank).")
    ap.add_argument("--processes", type=int, required=True)
    ap.add_argument("--devices", type=int, required=True,
                    help="fake CPU devices per process")
    ap.add_argument("--timeout", type=int, default=3600)
    ap.add_argument("cmd", nargs=argparse.REMAINDER,
                    help="command to run per rank (prefix with --)")
    args = ap.parse_args(argv)
    cmd = args.cmd[1:] if args.cmd[:1] == ["--"] else args.cmd
    if not cmd:
        ap.error("no command given — e.g. ... -- python "
                 "benchmarks/structure_sweep.py --tiny --processes 2")
    return launch(cmd, args.processes, args.devices, timeout=args.timeout)


if __name__ == "__main__":
    sys.exit(_main(sys.argv[1:]))
