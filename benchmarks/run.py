#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The parent (this process) never imports JAX. It starts the program's planner
and one worker that holds every local chip (``benchmarks/cluster.py``,
``benchmarks/worker.py``), has the cell's guest driver (``guests/<guest>.py``,
named by the cell's traffic file) do set-up, the measured window and the
correctness check through REST → planner → executor, then lets one reader a
metric (``end_to_end/<name>.py``, ``layer_metrics/<name>.py``) take its
number from the record, and prints the result as the last line of standard
output. ``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics.

No TPU, or fewer chips than the cell asks for: exit 3 and no result.
``--rehearse`` (tests only) runs the same flow on whatever backend there is,
on cells of a manifest given with ``--manifest``; every metric's value is
then null, because a CPU's number is never written under a device metric's
name.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import sys
import time

T_PROCESS_START = time.time()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXIT_FAILED, EXIT_NO_CHIP = 1, 3
# A safety net, not a budget: the first run of a cell in a checkout compiles
# and may take 1200 s; a warm run ends in well under 360 s by itself
DEADLINE_S = 1150


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--rehearse", action="store_true",
                    help="tests only: any backend, values printed as null")
    ap.add_argument("--control", default=None,
                    help="measurement of the limits only: also put the "
                         "reference in this lower precision in the "
                         "program's place and report its readings")
    ap.add_argument("--faults", nargs="*", default=None,
                    help="measurement of the limits only: faults to plant "
                         "in the reference put in the program's place")
    return ap.parse_args(argv)


def compare(numbers: dict, limits: dict) -> tuple:
    """Each number compared beside its limit; correct only if every limit
    has its number and holds it."""
    rows = {name: {"value": numbers.get(name), "limit": limit}
            for name, limit in limits.items()}
    ok = all(r["value"] is not None and r["value"] == r["value"]
             and r["value"] <= r["limit"] for r in rows.values())
    return ok, rows


def verdicts(record: dict, limits: dict) -> dict:
    """The control and every planted fault, each held to the limits as the
    program is: ``{"control": (correct, rows), "fault_x": ...}``. One that
    comes out correct shows that the limits separate nothing."""
    return {key: compare(numbers, limits)
            for key, numbers in record.items()
            if key == "control" or key.startswith("fault_")}


def read_metrics(manifest, kind: str, cell: dict, record: dict) -> dict:
    from benchmarks import cells

    out = {}
    folder = "end_to_end" if kind == "end_to_end" else "layer_metrics"
    for m in cells.metrics_of(manifest, kind, cell["name"]):
        value = cells.load_module(manifest, folder, m["name"]).read(record)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    args = parse(argv)
    sys.path.insert(0, ROOT)
    from benchmarks import cells
    from benchmarks.cluster import BenchFailed, Cluster, NoAccelerator
    from benchmarks.peaks import peaks_for

    if args.rehearse and os.environ.get("JAX_PLATFORMS") != "cpu":
        print("--rehearse is for tests under JAX_PLATFORMS=cpu",
              file=sys.stderr)
        return EXIT_FAILED
    manifest = cells.load_manifest(args.manifest)
    cell = cells.load_cell(manifest, args.workload)
    guest = cells.load_module(manifest, "guests", cell["guest"])
    limits = cell["traffic_values"]["check"]["limits"][cell["config"]]

    out_dir = os.path.join(ROOT, ".bench_out", cell["name"])
    shutil.rmtree(out_dir, ignore_errors=True)
    deadline = time.monotonic() + DEADLINE_S
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(EXIT_FAILED))
    cluster = Cluster.for_cell(ROOT, out_dir, args.manifest, cell["name"],
                               args.rehearse)
    failure = record = device = None
    try:
        cluster.wait_planner(deadline)
        device = cluster.worker_line("READY", deadline)
        t_ready = time.time()
        hosts = cluster.hosts()
        n = device["count"]
        if [(h["slots"], h["nDevices"]) for h in hosts] != [(n, n)]:
            raise BenchFailed(f"planner sees hosts {hosts}, worker has {n}")
        record = guest.drive(cluster, cell, args, deadline)
        record["setup_phases"] = dict(
            record.get("setup_phases", {}),
            worker_ready_s=t_ready - T_PROCESS_START)
    except NoAccelerator as e:
        failure = (EXIT_NO_CHIP, f"no accelerator for this cell ({e})")
    except Exception as e:  # noqa: BLE001 — the boundary: report and fail
        failure = (EXIT_FAILED, f"{type(e).__name__}: {e}")
    finally:
        last_words = cluster.stop()
    exits = [p.returncode for p in cluster.procs]
    if not failure and (any(exits) or "jax" in sys.modules):
        failure = (EXIT_FAILED, f"child exits {exits}, parent imported jax: "
                   f"{'jax' in sys.modules}")
    if not failure and any(record["compiles_in_window"].values()):
        failure = (EXIT_FAILED, "compiled inside the measured window: "
                   f"{record['compiles_in_window']}")
    if not failure and args.trace and not args.rehearse \
            and not record.get("trace"):
        failure = (EXIT_FAILED, "the traced window holds no device "
                   f"operation; planes: {record.get('planes')}")
    if failure:
        print(f"benchmark: {failure[1]} (logs under {out_dir})",
              file=sys.stderr)
        return failure[0]

    record.update(
        cell={k: cell[k] for k in ("name", "config", "traffic", "chips")},
        config=cell["config_values"], traffic=cell["traffic_values"],
        device=device, setup_s=record["window_start"] - T_PROCESS_START,
        peaks=None if args.rehearse else peaks_for(device["kind"]))
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = read_metrics(manifest, kind, cell, record)
    correct, compared = compare(record["numbers"], limits)
    if args.rehearse:
        metrics = {k: dict(v, value=None) for k, v in metrics.items()}

    with open(os.path.join(out_dir, "record.json"), "w") as f:
        json.dump(record, f)
    trace = record.get("trace") or {}
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"],
           "memory_peak_bytes": record["memory_peak_bytes"]}
    if args.trace and trace:
        dev.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
    line = {"correct": correct, "attempted": record["attempted"],
            "failed": record["failed"], "metrics": metrics, "device": dev}
    if args.trace and trace:
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    planted = verdicts(record, limits)
    for key, (ok, rows) in planted.items():
        line[key] = rows
        line[f"{key}_correct"] = ok
    if args.rehearse:
        line["rehearsal"] = True
    line["compared"] = compared

    print(json.dumps({"compiles_in_window": record["compiles_in_window"],
                      "compiles_whole_run": last_words.get("compiles"),
                      "setup_s": record["setup_s"],
                      "setup_phases": record["setup_phases"],
                      "window_s": record["window_s"],
                      "check_s": record.get("check_s"),
                      "loaded": record["loaded"]}))
    for name, row in compared.items():
        print(f"compared {name} = {row['value']} limit {row['limit']}",
              file=sys.stderr)
    print(f"correct = {correct}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    passed = [key for key, (ok, _) in planted.items() if ok]
    if passed:
        print(f"benchmark: {passed} came out correct: the limits do not "
              "tell them from the program", file=sys.stderr)
        return EXIT_FAILED
    return 0


if __name__ == "__main__":
    sys.exit(main())
