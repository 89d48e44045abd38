#!/usr/bin/env python3
"""The program's own spans in a traced run: where the runtime's phases lie
on the device trace's clock.

The program wraps the worker's side of every invocation in
``jax.profiler.TraceAnnotation`` spans named ``faabric:run_prep``,
``faabric:run`` and ``faabric:result_push``. Each carries the invocation's
``msg_id`` (as the text ``m<id>``: an id has more than 64 bits), the
lifecycle ledger as it stood when the span opened (``hin``, ``adm``,
``qex``, ``sch``, ``jnl``, ``dsp``, ``eqx``, ``rns``, ``rne``:
``CLOCK_MONOTONIC`` nanoseconds, the planner's stamps too; ``rcu`` and
``stx``: durations) and ``mono_ns``, the monotonic clock at the span's own
start, which ties that clock to the profiler's.

Two steps, as in ``trace_reduce``, so that the arithmetic needs no profiler:

- :func:`extract` reads the ``.xplane.pb`` with ``jax.profiler.ProfileData``
  and keeps the host plane's events named ``faabric:*`` and
  ``bench:request*`` with their stats, and each device plane's
  ``XLA Modules`` line (one event a run of a jitted program; the
  operations line has a hundred thousand events a second of decode).
  :func:`load` runs it in a child process under ``JAX_PLATFORMS=cpu``, since
  the benchmark's parent never imports JAX, and keeps the result as
  ``program_spans.json`` beside the record, so that every reader of one run
  parses once.
- everything else works on that dict: ``{"host": [{"name", "start_ns",
  "dur_ns", "stats"}, ...], "modules": {plane: [[start_ns, dur_ns], ...]}}``.

A trace of a program that has no such spans gives every reader None.

    python3 benchmarks/program_spans.py <out_dir>

prints, for the traced run whose logs lie in ``out_dir``
(``.bench_out/<cell>``), every request's phases, the idle time outside
``run`` by the phase it fell in, and ``launch_ms`` against the phases.
"""

from __future__ import annotations

import functools
import json
import os
import subprocess
import sys
import time
from statistics import mean, median

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPAN_PREFIX = "faabric:"
REQUEST_PREFIX = "bench:request"
DEVICE_PLANE_PREFIX = "/device:TPU:"
MODULES_LINE = "XLA Modules"
CACHE_NAME = "program_spans.json"
CHILD_TIMEOUT_S = 120

# The ledger's keys that are durations, not points on the clock
DURATION_KEYS = ("stx", "rcu")
# The inbound phase that the gap ending at a stamp belongs to. A recovery
# requeue (``rqu``) ends a gap that is no phase's: the first attempt and
# the detection of its death.
PHASE_OF_STAMP = {
    "adm": "ingress", "qex": "ingress",
    "sch": "planner", "jnl": "planner", "dsp": "planner",
    "eqx": "executor_queue",
    "rns": "run_prep",
}


# ---------------------------------------------------------------------------
# From the profiler's file to the dict (the child's half)
# ---------------------------------------------------------------------------

def extract(xplane_path: str) -> dict:
    from jax.profiler import ProfileData

    t0 = time.time()
    data = ProfileData.from_file(xplane_path)
    out: dict = {"host": [], "modules": {}}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    out["modules"][plane.name] = [
                        [int(e.start_ns), int(e.duration_ns)]
                        for e in line.events]
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name
                if name.startswith((SPAN_PREFIX, REQUEST_PREFIX)):
                    out["host"].append({
                        "name": name, "start_ns": int(e.start_ns),
                        "dur_ns": int(e.duration_ns),
                        "stats": {k: v for k, v in e.stats
                                  if isinstance(v, (int, float, str))}})
    out["host"].sort(key=lambda e: e["start_ns"])
    out["extract_s"] = time.time() - t0
    return out


# ---------------------------------------------------------------------------
# Finding and keeping it (the parent's half)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def load(out_dir: str):
    """The spans of the traced run whose trace lies under
    ``<out_dir>/trace``; None where there is no trace or it cannot be
    read."""
    from benchmarks import trace_reduce

    cache = os.path.join(out_dir, CACHE_NAME)
    if not os.path.isfile(cache):
        try:
            xplane = trace_reduce.find_xplane(os.path.join(out_dir, "trace"))
        except FileNotFoundError:
            return None
        t0 = time.time()
        try:
            child = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--extract",
                 xplane, cache],
                env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
                timeout=CHILD_TIMEOUT_S, capture_output=True, text=True)
        except subprocess.TimeoutExpired:
            print(f"program_spans: reading {xplane} took over "
                  f"{CHILD_TIMEOUT_S} s", file=sys.stderr)
            return None
        if child.returncode != 0 or not os.path.isfile(cache):
            print(f"program_spans: could not read {xplane}: "
                  f"{child.stderr[-500:]}", file=sys.stderr)
            return None
        print(f"program_spans: read {xplane} in {time.time() - t0:.2f} s",
              file=sys.stderr)
    with open(cache) as f:
        return json.load(f)


def of_record(record: dict):
    """The spans of the run that made this record (``run.py`` keeps a
    cell's logs and trace under ``.bench_out/<cell>``)."""
    return load(os.path.join(ROOT, ".bench_out", record["cell"]["name"]))


# ---------------------------------------------------------------------------
# Arithmetic on the dict
# ---------------------------------------------------------------------------

def invocations(spans: dict) -> list:
    """One entry for each invocation whose ``faabric:run`` span the trace
    holds whole: ``{"msg_id", "lc" (the ledger: the union of its spans'
    stats), "offset_ns" (profiler clock − monotonic clock), "spans":
    {label: [start_ns, dur_ns]}}``, in the order they ran."""
    by_id: dict = {}
    for e in (spans or {}).get("host", []):
        if not e["name"].startswith(SPAN_PREFIX):
            continue
        stats = dict(e["stats"])
        msg_id, mono = stats.pop("msg_id", None), stats.pop("mono_ns", None)
        if msg_id is None or mono is None:
            continue
        label = e["name"][len(SPAN_PREFIX):]
        inv = by_id.setdefault(msg_id, {"msg_id": msg_id, "lc": {},
                                        "spans": {}})
        inv["lc"].update(stats)
        inv["spans"][label] = [e["start_ns"], e["dur_ns"]]
        if label == "run":
            inv["offset_ns"] = e["start_ns"] - mono
    return sorted((i for i in by_id.values() if "run" in i["spans"]),
                  key=lambda i: i["spans"]["run"][0])


def request_spans(spans: dict) -> list:
    return [e for e in (spans or {}).get("host", [])
            if e["name"].startswith(REQUEST_PREFIX)]


def requests(spans: dict) -> list:
    """The invocations that served a request: those whose ``run`` holds a
    ``bench:request`` span (the guest's other operations, such as starting
    the profiler, are invocations too). Every invocation where the guest
    marks no request."""
    marks = request_spans(spans)
    found = invocations(spans)
    if not marks:
        return found
    out = []
    for inv in found:
        start, dur = inv["spans"]["run"]
        inside = [m for m in marks if start <= m["start_ns"]
                  and m["start_ns"] + m["dur_ns"] <= start + dur]
        if inside:
            out.append(dict(inv, request=inside[0]["name"]))
    return out


def stamps_of(inv: dict) -> list:
    """The ledger's stamps, time-sorted, as ``(ns on the trace's clock,
    key)``."""
    return sorted((int(v) + inv["offset_ns"], k)
                  for k, v in inv["lc"].items() if k not in DURATION_KEYS)


def phase_intervals(inv: dict) -> list:
    """``(phase, start_ns, end_ns)`` on the trace's clock for the inbound
    phases (each gap belongs to the phase of the stamp that ends it, in
    the order of time, so a requeued ledger's gaps fall where they
    happened) and the worker's ``result_push`` span."""
    stamps = stamps_of(inv)
    out = [(PHASE_OF_STAMP[key], stamps[i - 1][0], t)
           for i, (t, key) in enumerate(stamps)
           if i and key in PHASE_OF_STAMP]
    if "result_push" in inv["spans"]:
        start, dur = inv["spans"]["result_push"]
        out.append(("result_push", start, start + dur))
    return out


def phases_ms(inv: dict) -> dict:
    out: dict = {}
    for phase, start, end in phase_intervals(inv):
        out[phase] = out.get(phase, 0.0) + (end - start) / 1e6
    if "rcu" in inv["lc"]:
        out["run_host_cpu"] = inv["lc"]["rcu"] / 1e6
    return out


def phase_ms(spans: dict, phase: str, over=median):
    """One phase's milliseconds over the traced requests (their median,
    or what ``over`` makes of the list); None where no traced request has
    it."""
    values = [p[phase] for p in map(phases_ms, requests(spans))
              if phase in p]
    return over(values) if values else None


def window_of(spans: dict):
    """As ``trace_reduce.window_of``: first request's start to last
    request's end; the modules' extent where the guest marks none."""
    marks = request_spans(spans)
    if marks:
        return (min(m["start_ns"] for m in marks),
                max(m["start_ns"] + m["dur_ns"] for m in marks))
    runs = [e for ev in spans["modules"].values() for e in ev]
    return (min(s for s, _d in runs), max(s + d for s, d in runs))


def idle_outside_run(spans: dict):
    """``(window_ns, gaps by device plane)``: the stretches of the window
    in which no XLA module runs on the chip and no ``faabric:run`` span is
    open. None where the trace has no device plane or no such span."""
    from benchmarks.trace_reduce import _union

    runs = [i["spans"]["run"] for i in invocations(spans)]
    if not runs or not any((spans or {}).get("modules", {}).values()):
        return None
    w0, w1 = window_of(spans)
    gaps = {}
    for plane, modules in spans["modules"].items():
        busy = _union([[max(s, w0), min(s + d, w1)]
                       for s, d in modules + runs if s < w1 and s + d > w0])
        edges = [w0] + [t for iv in busy for t in iv] + [w1]
        gaps[plane] = [(edges[i], edges[i + 1])
                       for i in range(0, len(edges), 2)
                       if edges[i + 1] > edges[i]]
    return w1 - w0, gaps


def idle_outside_run_share(spans: dict):
    """Percent of the window, mean over the chips."""
    found = idle_outside_run(spans)
    if found is None:
        return None
    window, gaps = found
    idle = [sum(e - s for s, e in g) for g in gaps.values()]
    return 100.0 * sum(idle) / len(idle) / window


def idle_by_phase(spans: dict):
    """Seconds of idle time outside ``run`` (first chip) by the phase of
    the traced requests it fell in; ``outside the ledger`` is the rest:
    the way back to the client and the client itself."""
    found = idle_outside_run(spans)
    if found is None:
        return None
    _window, gaps = found
    phases = [iv for inv in requests(spans) for iv in phase_intervals(inv)]
    out: dict = {}
    for g0, g1 in gaps[sorted(gaps)[0]]:
        left = g1 - g0
        for phase, p0, p1 in phases:
            inside = min(g1, p1) - max(g0, p0)
            if inside > 0:
                out[phase] = out.get(phase, 0.0) + inside / 1e9
                left -= inside
        out["outside the ledger"] = out.get("outside the ledger", 0.0) \
            + max(left, 0) / 1e9
    return out


# ---------------------------------------------------------------------------

def summary(out_dir: str) -> dict:
    """Per-request phases, and ``launch_ms`` (the benchmark's two
    ``time.time()`` stamps) against them: what is left is the two ends the
    program cannot see, the client's POST up to ``hin`` and ``rns`` up to
    the guest's first line (of which ``rns`` → the request's span is
    seen)."""
    spans = load(out_dir)
    if spans is None:
        return {"spans": None}
    record = {}
    if os.path.isfile(os.path.join(out_dir, "record.json")):
        with open(os.path.join(out_dir, "record.json")) as f:
            record = json.load(f)
    by_index = {f"{REQUEST_PREFIX}#{r['index']}": r
                for r in record.get("requests", []) if "guest_start" in r}
    rows = []
    for inv in requests(spans):
        row = {"msg_id": inv["msg_id"], "request": inv.get("request"),
               **phases_ms(inv)}
        run0, run_dur = inv["spans"]["run"]
        row["run"] = run_dur / 1e6
        logged = by_index.get(inv.get("request"))
        if logged:
            mark = next(m for m in request_spans(spans)
                        if m["name"] == inv["request"])
            inbound = sum(row.get(p, 0.0) for p in
                          ("ingress", "planner", "executor_queue",
                           "run_prep"))
            row["launch"] = (logged["guest_start"] - logged["posted"]) * 1e3
            row["run_start_to_request_span"] = (mark["start_ns"] - run0) / 1e6
            row["launch_less_phases"] = row["launch"] - inbound
        rows.append(row)
    return {"requests": rows,
            "idle_outside_run_share": idle_outside_run_share(spans),
            "idle_by_phase_s": idle_by_phase(spans),
            "extract_s": spans.get("extract_s"),
            "events": {"host": len(spans["host"]),
                       "modules": {p: len(e)
                                   for p, e in spans["modules"].items()}}}


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) == 3 and argv[0] == "--extract":
        out = extract(argv[1])
        with open(argv[2] + ".tmp", "w") as f:
            json.dump(out, f)
        os.replace(argv[2] + ".tmp", argv[2])
        return 0
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    print(json.dumps(summary(os.path.abspath(argv[0])), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
