"""Readings of a cell's correctness numbers for sound runs and for the
control, over several seeds in one process.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \
        --seconds <s>

For each seed: set-up and a window as in a run, then the numbers the run
compares for the scheduler's answers ("sound") and for the control (the
plain reference computed in bfloat16, the precision below the float32 the
configuration states, put in the scheduler's place).  A limit lies
between the largest sound reading and the smallest control reading; the
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(HERE, "lib"), HERE]

import device  # noqa: E402
import registry  # noqa: E402
import run  # noqa: E402


def readings(cell, seed: int, seconds: float) -> dict:
    sys.path.insert(0, os.path.join(registry.ROOT, "src"))
    drv = registry.driver(cell.driver)
    state = drv.setup(cell, seed, run.say)
    drv.window_run(state, seconds)
    drv.release(state)
    limits = cell.config["limits"]
    _, _, sound = drv.check(state, limits)
    _, _, control = drv.judge(drv.control_rows(state), limits)
    return {"seed": seed, "sound": {n: v for n, v, _ in sound},
            "control": {n: v for n, v, _ in control}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = registry.cell(args.workload, registry.benchmark())
    devs = device.require_tpu(cell.chips)
    import clock
    clock.use_compile_cache(registry.ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        r = readings(cell, seed, args.seconds)
        r["device"] = device.record(devs)
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
