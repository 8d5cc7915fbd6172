"""Run one benchmark cell on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout.  The run finds the cell, its configuration,
its traffic and its driver by name (see ``chipbench/lib/registry.py``),
checks that JAX sees the TPUs the cell asks for (there is no CPU
fallback), sets up (inputs from the seed, compilation, warm-up), runs the
measured window, holds every answer of the window against the plain
reference, and prints one JSON line last on stdout.  ``--trace 0``
reports the cell's end-to-end metrics; ``--trace 1`` traces the window's
first unit with the profiler and reports the per-layer metrics.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(HERE, "lib")]

import clock as compile_clock  # noqa: E402
import device  # noqa: E402
import devtrace  # noqa: E402
import registry  # noqa: E402


def say(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def traced_unit(logdir: str, part=None):
    """A context manager factory: the profiler over one unit, the traced
    span marked on the host so the trace's window can be found.  ``part``
    ``(after_s, length_s)`` traces only that slice of the unit, from a
    second thread, for units whose whole trace would be too large."""
    import contextlib
    import threading

    import jax

    # Host events are the runtime's own: which jitted function is being
    # dispatched, what the runtime waits on.  Python calls are not traced.
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0

    @contextlib.contextmanager
    def whole():
        with jax.profiler.trace(logdir, profiler_options=options):
            with jax.profiler.TraceAnnotation(devtrace.WINDOW):
                yield

    def record():
        jax.profiler.start_trace(logdir, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation(devtrace.WINDOW):
                time.sleep(part[1])
        finally:
            jax.profiler.stop_trace()

    @contextlib.contextmanager
    def sliced():
        timer = threading.Timer(part[0], record)
        timer.start()
        try:
            yield
        finally:
            timer.join()

    return sliced if part else whole


def execute(cell: registry.Cell, bench: dict, seed: int, seconds: float,
            traced: bool, devs: list | None, root: str = registry.ROOT,
            t0: float = T_PROCESS) -> dict:
    """Everything after the chip check; ``devs`` None skips reading the
    chip's memory (CPU rehearsals and tests)."""
    sys.path.insert(0, os.path.join(root, "src"))
    clk = compile_clock.CompileClock().install()
    cache = compile_clock.use_compile_cache(root)
    drv = registry.driver(cell.driver)
    state = drv.setup(cell, seed, say)
    setup_s = time.perf_counter() - t0
    before = clk.snapshot()
    say(f"set-up {setup_s} s: {before['compiles']} programs compiled or "
        f"loaded in {before['compile_s']} s; persistent cache {cache}: "
        f"{before['cache_hits']} hits, {before['cache_misses']} misses")

    with tempfile.TemporaryDirectory() as logdir:
        drv.window_run(state, seconds, traced=traced_unit(
            logdir, getattr(drv, "TRACE_PART", None)) if traced else None)
        after = clk.snapshot()
        new = after["compiles"] - before["compiles"]
        units = state.units
        secs = sorted(u.end - u.start for u in units)
        say(f"window: {len(units)} units in "
            f"{units[-1].end - units[0].start} s; unit seconds min "
            f"{secs[0]}, median {secs[len(secs) // 2]}, max {secs[-1]}, "
            f"first {units[0].end - units[0].start}; programs compiled or "
            f"loaded inside it: {new}")
        if new:
            raise RuntimeError(f"{new} programs were compiled or loaded "
                               f"inside the measured window")
        summary = devtrace.reduce(logdir) if traced else None
    memory = device.memory_peak_bytes(devs) if devs else 0
    e2e = drv.end_to_end(state)
    spans = drv.spans(state)
    drv.release(state)

    attempted, failed, checks = drv.check(state, cell.config["limits"])
    correct = failed == 0 and all(v <= lim for _, v, lim in checks)

    kind = "per_layer" if traced else "end_to_end"
    metrics = {}
    ctx = {"cell": cell, "spans": spans, "trace": summary,
           "device_kind": devs[0].device_kind if devs else None}
    for m in registry.metrics_of(bench, cell.name, kind):
        if m["name"] == "setup_s":
            value = setup_s
        elif traced:
            value = registry.reader(m["name"])(ctx)
        else:
            value = e2e[m["name"]]
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics,
              "device": (device.record(devs) if devs else {})}
    if devs:
        result["device"]["memory_peak_bytes"] = memory
    if summary is not None:
        result["device"]["busy_s"] = summary["busy_s"]
        result["device"]["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in checks}
    return result


def main(argv=None) -> int:
    args = parse(argv)
    bench = registry.benchmark()
    cell = registry.cell(args.workload, bench)
    devs = device.require_tpu(cell.chips)
    say(f"{args.workload} seed {args.seed} on {device.record(devs)}")
    result = execute(cell, bench, args.seed, args.seconds,
                     bool(args.trace), devs)
    for name, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} {c['value']} limit {c['limit']} {ok}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
