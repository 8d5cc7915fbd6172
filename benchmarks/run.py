"""Benchmark harness: one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--instances N] [--only fig5]``

Prints a CSV row per result line and writes per-benchmark CSVs under
``experiments/bench/``.  Defaults are sized for this 1-core container;
``--instances 1000`` reproduces the paper's batch size.
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks import (fig4_makespan, fig5_stretch, fig6_regions,
                        fig7_carbon_vs_energy, learned_gate,
                        online_vs_offline, stream_serve, structure_sweep,
                        table1a_servers, table1b_tasks)
from benchmarks.common import use_compile_cache

BENCHES = {
    "fig4": fig4_makespan.run,
    "fig5": fig5_stretch.run,
    "fig6": fig6_regions.run,
    "fig7": fig7_carbon_vs_energy.run,
    "table1a": table1a_servers.run,
    "table1b": table1b_tasks.run,
    "online": online_vs_offline.run,   # beyond-paper: price of online
    "structure": structure_sweep.run_harness,  # savings vs DAG structure
    "learned": learned_gate.run_harness,   # learned vs fixed gate thetas
    "stream": stream_serve.run_harness,    # streaming dispatch under load
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--instances", type=int, default=16)
    ap.add_argument("--only", default=None,
                    help="comma-separated subset, e.g. fig5,table1a")
    args = ap.parse_args()
    use_compile_cache()
    names = (args.only.split(",") if args.only else list(BENCHES))

    t0 = time.time()
    for name in names:
        rows = BENCHES[name](instances=args.instances)
        for row in rows:
            print(",".join(f"{k}={v}" for k, v in row.items()), flush=True)
    print(f"# total {time.time() - t0:.0f}s over {len(names)} benchmarks, "
          f"{args.instances} instances each", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
