#!/usr/bin/env python3
"""The readings a cell's limits are set from, over many seeds in one warm
process: the numbers the correctness check compares, for the program, for
the lower-precision control and for planted faults.

    python3 benchmarks/limits.py --workload <name> --seeds 1 2 3 ... \\
        [--seconds 2] [--control fp8] [--faults half_batch ...]

One planner and one worker serve every seed (each request names its seed),
so set-up is paid once. Every seed goes through the cell's own ``drive``:
the same set-up, a short window at the cell's own load, the same check. A
line of JSON a seed on standard output, then the largest program reading
and the smallest control and fault readings of each number. Every seed's
program, control and faults are held to the cell's limits as a run holds
them: exit 1 where a program's reading fails them or a control or a fault
passes them. The benchmark's runs never call this; it needs the chip like
they do.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEADLINE_S = 3300  # a safety net under one chip call


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--control", default=None)
    ap.add_argument("--faults", nargs="*", default=None)
    ap.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    args.trace = 0
    sys.path.insert(0, ROOT)
    from benchmarks import cells
    from benchmarks.cluster import Cluster
    from benchmarks.run import compare, verdicts

    manifest = cells.load_manifest(args.manifest)
    cell = cells.load_cell(manifest, args.workload)
    guest = cells.load_module(manifest, "guests", cell["guest"])
    limits = cell["traffic_values"]["check"]["limits"][cell["config"]]
    out_dir = os.path.join(ROOT, ".bench_out", cell["name"] + ".limits")
    deadline = time.monotonic() + DEADLINE_S
    cluster = Cluster.for_cell(ROOT, out_dir, args.manifest, cell["name"],
                               args.rehearse)
    lowest: dict = {}
    highest: dict = {}
    unsound: list = []
    try:
        cluster.wait_planner(deadline)
        cluster.worker_line("READY", deadline)
        for seed in args.seeds:
            args.seed = seed
            t0 = time.time()
            record = guest.drive(cluster, cell, args, deadline)
            row = {"seed": seed, "seconds": time.time() - t0,
                   "program": record["numbers"],
                   "correct": compare(record["numbers"], limits)[0]}
            if not row["correct"]:
                unsound.append((seed, "program"))
            for name, value in row["program"].items():
                highest[name] = max(highest.get(name, value), value)
            for key, (ok, _) in verdicts(record, limits).items():
                row[key] = record[key]
                row[f"{key}_correct"] = ok
                if ok:
                    unsound.append((seed, key))
                at = lowest.setdefault(key, {})
                for name, value in record[key].items():
                    at[name] = min(at.get(name, value), value)
            print(json.dumps(row), flush=True)
    finally:
        cluster.stop()
    print(json.dumps({"program_largest": highest, "smallest": lowest,
                      "limits": limits,
                      "program_failed_or_planted_passed": unsound}),
          flush=True)
    return 1 if unsound else 0


if __name__ == "__main__":
    sys.exit(main())
