#!/usr/bin/env python3
"""Device time by the program's scopes: the trace's events joined to the
instructions of the programs that ran.

The program names where an instruction comes from with two levels of
``jax.named_scope`` (``faabric_tpu/models/scopes.py``: a phase, ``prefill``
/ ``decode_step`` / ``loss`` / ``optimizer``, and inside it a sub-layer,
``attention`` / ``feed_forward`` / ...), which reach the ``op_name`` of
every instruction of the optimized HLO. A device event of the trace's
``XLA Ops`` line is named by its instruction's text and carries no
``op_name``; the trace's plane ``/host:metadata`` holds, for every module
that ran, an event metadata named like the module's events of the ``XLA
Modules`` line (``jit__generate_impl(12)``) with a statistic ``Hlo Proto``:
the module as it was compiled, every instruction with its ``op_name``, a
fusion with the computation it calls. So an event is joined by (the module
whose span holds it, its instruction's name), and no rule over kinds and
shapes is needed.

Two steps, as in ``program_spans``, so that the arithmetic needs no
profiler and no protobuf:

- :func:`extract` reads the ``.xplane.pb`` as a raw ``XSpace``
  (``tensorflow.tsl.profiler.protobuf.xplane_pb2``;
  ``jax.profiler.ProfileData`` does not show a metadata's statistics) into
  ``trace_reduce``'s compact form with the modules' spans and the tables
  beside it: ``{"devices": {plane: [[name, start_ns, dur_ns], ...]},
  "modules": {plane: [[module, start_ns, dur_ns], ...]}, "host": [[name,
  start_ns, dur_ns], ...], "tables": {module: {instruction: [op_name,
  [op_names of what is fused into it], first reader]}}}``.
- :func:`reduce` works on that dict alone: own device time (nested events
  taken out, as ``trace_reduce.self_times``: a ``while`` and its body are
  not counted twice) of the busiest chip's events inside the traced
  window, by scope.

A fusion is charged to its root's scope (its own ``op_name`` is its
root's), and the scopes of what was fused into it are kept beside it
(``touches``). An instruction with no ``op_name`` of its own is the
compiler's, made for the instruction that reads its result (the copies
XLA's memory-space assignment makes ahead of an operand's reader:
``copy-start`` / ``copy-done``, ``slice-start`` / ``slice-done``, the
``ConcatBitcast`` that joins the slices): it takes its first reader's
scope.

:func:`load` runs both steps in a child process under ``JAX_PLATFORMS=cpu``
(the benchmark's parent imports neither JAX nor TensorFlow) and keeps the
result as ``scope_times.json`` beside the record; every reader parses that.
A run that was not traced, a trace without a device plane (a rehearsal on
the CPU), a program without ``models/scopes.py`` and a join that covers
under 90% of the device's busy time (the names in the trace are not the
vocabulary's: a program loaded from a compile cache that an older tree
filled, since the cache's key leaves metadata out) give every reader None.

    python3 benchmarks/scope_times.py <out_dir> [top]

prints, for the traced run whose logs lie in ``out_dir``
(``.bench_out/<cell>``), the whole reduction with ``top`` instructions a
scope.
"""

from __future__ import annotations

import bisect
import collections
import functools
import importlib.util
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCOPES_FILE = os.path.join(ROOT, "faabric_tpu", "models", "scopes.py")
DEVICE_PLANE_PREFIX = "/device:TPU:"
METADATA_PLANE = "/host:metadata"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HLO_STAT = "Hlo Proto"
HOST_SPAN_PREFIX = "bench:"
CACHE_NAME = "scope_times.json"
CHILD_TIMEOUT_S = 120
MIN_COVERAGE = 90.0
NO_SCOPE = "-"
# How many readers the join follows from an instruction without a scope
MAX_HOPS = 8


@functools.lru_cache(maxsize=None)
def program_scopes():
    """The program's vocabulary, loaded by its file (the package would
    import JAX); None where the program has none."""
    if not os.path.isfile(SCOPES_FILE):
        return None
    spec = importlib.util.spec_from_file_location("program_scopes",
                                                  SCOPES_FILE)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def key_of(op_name: str) -> str:
    """``phase/sublayer`` of an ``op_name``, ``-`` for a level it lacks."""
    phase, sublayer = program_scopes().of_op_name(op_name)
    return f"{phase or NO_SCOPE}/{sublayer or NO_SCOPE}"


def covered(key: str) -> bool:
    """Whether a scope says where the time went (``scopes.placed``)."""
    return program_scopes().placed(*(None if level == NO_SCOPE else level
                                     for level in key.split("/")))


# ---------------------------------------------------------------------------
# From the profiler's file to the compact form (the child's half)
# ---------------------------------------------------------------------------

def table_of(hlo_module) -> dict:
    """instruction → [op_name, the op_names fused into it, its first
    reader] of one ``HloModuleProto``. A fusion's own ``op_name`` is its
    root's; where it has none the called computation's root gives it."""
    by_id = {c.id: c for c in hlo_module.computations}
    table: dict = {}
    for comp in hlo_module.computations:
        names = {i.id: i.name for i in comp.instructions}
        reader: dict = {}
        for i in comp.instructions:
            for operand in i.operand_ids:
                reader.setdefault(names.get(operand), i.name)
        for i in comp.instructions:
            op_name, fused = i.metadata.op_name, []
            if i.opcode == "fusion":
                for called in i.called_computation_ids:
                    inner = by_id[called].instructions
                    fused = sorted({j.metadata.op_name for j in inner
                                    if j.metadata.op_name})
                    if not op_name:
                        root = {j.id: j for j in inner}[by_id[called].root_id]
                        op_name = root.metadata.op_name
            table[i.name] = [op_name, fused,
                             None if op_name else reader.get(i.name)]
    return table


def extract(xplane_path: str) -> dict:
    from tensorflow.compiler.xla.service import hlo_pb2
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    from benchmarks.trace_reduce import instruction_of

    space = xplane_pb2.XSpace()
    with open(xplane_path, "rb") as f:
        space.ParseFromString(f.read())
    out: dict = {"devices": {}, "modules": {}, "host": [], "tables": {}}
    for plane in space.planes:
        names = {k: m.name for k, m in plane.event_metadata.items()}

        def events_of(line, label):
            return [[label(names[e.metadata_id]),
                     line.timestamp_ns + e.offset_ps / 1e3,
                     e.duration_ps / 1e3] for e in line.events]

        if plane.name == METADATA_PLANE:
            stats = {k: m.name for k, m in plane.stat_metadata.items()}
            for meta in plane.event_metadata.values():
                for stat in meta.stats:
                    if stats.get(stat.metadata_id) == HLO_STAT:
                        proto = hlo_pb2.HloProto()
                        proto.ParseFromString(stat.bytes_value)
                        out["tables"][meta.name] = table_of(proto.hlo_module)
        elif plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out["devices"][plane.name] = events_of(
                        line, lambda n: " ".join(instruction_of(n)).strip())
                elif line.name == MODULES_LINE:
                    out["modules"][plane.name] = events_of(line, str)
        else:
            for line in plane.lines:
                out["host"] += [e for e in events_of(line, str)
                                if e[0].startswith(HOST_SPAN_PREFIX)]
    out["host"].sort(key=lambda e: e[1])
    return out


# ---------------------------------------------------------------------------
# Arithmetic on the compact form
# ---------------------------------------------------------------------------

def _scope_of(table: dict, instruction: str) -> tuple:
    """(scope key or None where the table lacks the instruction, the scope
    keys it touches). An instruction without an ``op_name`` follows its
    readers to the first that has one."""
    entry = table.get(instruction)
    if entry is None:
        return None, ()
    for _ in range(MAX_HOPS):
        if entry[0] or entry[2] not in table:
            break
        entry = table[entry[2]]
    own = key_of(entry[0])
    touches = {key_of(name) for name in entry[1]} | {own}
    return own, sorted(t for t in touches if covered(t))


def reduce(compact: dict, top: int = 10) -> dict | None:
    """The busiest chip's own device time inside the traced window, by
    scope. ``by_scope`` {"phase/sublayer": seconds, "-" for a level the
    ``op_name`` lacks}; ``unknown_s``: events whose instruction no table
    holds; ``touching_s`` {scope: seconds of the events charged to it or
    fused with something of it}; ``mixed_s``: events that touch more than
    one scope; ``top``: the instructions of each scope that took most, by
    kind and result shape. None where the trace holds no device
    operation."""
    from benchmarks import trace_reduce

    if program_scopes() is None or not any(compact["devices"].values()):
        return None
    w0, w1 = trace_reduce.window_of(compact)
    plane = max(compact["devices"], key=lambda p: sum(
        d for _n, _s, d in compact["devices"][p]))
    modules = sorted(compact.get("modules", {}).get(plane, []),
                     key=lambda m: m[1])
    starts = [m[1] for m in modules]

    def module_at(start: float):
        at = bisect.bisect_right(starts, start) - 1
        if at >= 0 and start < modules[at][1] + modules[at][2]:
            return modules[at][0]
        return None

    inside = [[n, max(s, w0), min(s + d, w1) - max(s, w0)]
              for n, s, d in compact["devices"][plane]
              if s < w1 and s + d > w0]
    # an instruction's name is its module's own: keyed by both, own time
    # and count, then each key once through the join
    keyed = [[(module_at(s), n), s, d] for n, s, d in inside]
    count = collections.Counter(key for key, _s, _d in keyed)
    by_scope: dict = {}
    touching: dict = {}
    tops: dict = {}
    unknown = mixed = 0.0
    for (module, name), own in trace_reduce.self_times(keyed).items():
        instruction, _, shape = name.partition(" ")
        scope, touches = _scope_of(compact["tables"].get(module, {}),
                                   instruction)
        if scope is None:
            unknown += own
            scope = key_of("")
        by_scope[scope] = by_scope.get(scope, 0.0) + own
        for t in touches:
            touching[t] = touching.get(t, 0.0) + own
        mixed += own if len(touches) > 1 else 0.0
        label = f"{trace_reduce.kind_of(instruction)} {shape}".strip()
        row = tops.setdefault(scope, {}).setdefault(
            label, {"seconds": 0.0, "count": 0, "touches": touches})
        row["seconds"] += own
        row["count"] += count[module, name]

    merged = trace_reduce._union([[s, s + d] for _n, s, d in inside])
    busy = sum(e - s for s, e in merged) / 1e9
    return {
        "chip": plane,
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy,
        "events": len(inside),
        "host_spans": len(compact["host"]),
        "modules": sorted({m[0] for m in modules if m[1] < w1
                           and m[1] + m[2] > w0}),
        "by_scope": by_scope,
        "covered_s": sum(s for k, s in by_scope.items() if covered(k)),
        "unknown_s": unknown,
        "touching_s": touching,
        "mixed_s": mixed,
        "top": {scope: sorted(
            ([label, row["seconds"], row["count"], row["touches"]]
             for label, row in rows.items()), key=lambda r: -r[1])[:top]
            for scope, rows in tops.items()},
    }


def tables_summary(tables: dict) -> dict:
    """module → [its instructions, those whose own ``op_name`` gives a
    phase and a sub-layer]: whether the names a trace holds are the
    vocabulary's, whatever ran on the device."""
    return {module: [len(table), sum(covered(key_of(entry[0]))
                                     for entry in table.values())]
            for module, table in tables.items()}


# ---------------------------------------------------------------------------
# Finding and keeping it (the parent's half)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def load(out_dir: str):
    """The reduction of the traced run whose trace lies under
    ``<out_dir>/trace``; None where there is no trace, it cannot be read,
    it holds no device plane, or the program has no vocabulary."""
    from benchmarks import trace_reduce

    cache = os.path.join(out_dir, CACHE_NAME)
    if not os.path.isfile(cache):
        if program_scopes() is None:
            return None
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
            print(f"scope_times: reading {xplane} took over "
                  f"{CHILD_TIMEOUT_S} s", file=sys.stderr)
            return None
        if child.returncode != 0 or not os.path.isfile(cache):
            print(f"scope_times: could not read {xplane}: "
                  f"{child.stderr[-500:]}", file=sys.stderr)
            return None
        print(f"scope_times: read {xplane} in {time.time() - t0:.2f} s",
              file=sys.stderr)
    with open(cache) as f:
        return json.load(f)


def of_record(record: dict):
    """The reduction of the run that made this record, where it was traced
    on a device and the join holds: at least ``MIN_COVERAGE`` percent of
    the device's busy time falls on instructions with a phase and a
    sub-layer of the vocabulary."""
    if not record.get("trace") or "cell" not in record:
        return None
    times = load(os.path.join(ROOT, ".bench_out", record["cell"]["name"]))
    if not times or not times.get("chip") \
            or coverage(times) < MIN_COVERAGE:
        return None
    return times


# ---------------------------------------------------------------------------
# What the readers take from it (percent)
# ---------------------------------------------------------------------------

def coverage(times: dict) -> float:
    return 100.0 * times["covered_s"] / times["busy_s"] \
        if times["busy_s"] > 0 else 0.0


def coverage_of(record: dict):
    times = of_record(record)
    return coverage(times) if times else None


def phase_s(times: dict, phase: str, sublayers=None) -> float:
    """Seconds under a phase: all of it, or these sub-layers of it."""
    return sum(s for key, s in times["by_scope"].items()
               if key.split("/")[0] == phase
               and (sublayers is None or key.split("/")[1] in sublayers))


def share_of_phase(record: dict, phase: str, sublayers):
    times = of_record(record)
    whole = phase_s(times, phase) if times else 0.0
    return 100.0 * phase_s(times, phase, sublayers) / whole \
        if whole > 0 else None


def share_of_busy(record: dict, phase: str):
    times = of_record(record)
    return 100.0 * phase_s(times, phase) / times["busy_s"] if times else None


def touching_s(times: dict, phase: str) -> float:
    """Seconds of the events charged to a phase or fused with something
    of it."""
    return sum(s for key, s in times["touching_s"].items()
               if key.split("/")[0] == phase)


def adamw_call(n_params: int) -> dict:
    """One AdamW update over float32 leaves: the parameter, its gradient
    and both moments read, the parameter and both moments written, 7 × 4
    bytes a parameter; the moments' two updates, their corrections, the
    root, the decay and the step at 16 operations a parameter.
    ``flops.least_seconds`` takes these keys."""
    return {"flops": 16.0 * n_params, "bytes": 28.0 * n_params}


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    sys.path.insert(0, ROOT)
    if len(argv) == 3 and argv[0] == "--extract":
        t0 = time.time()
        compact = extract(argv[1])
        t1 = time.time()
        out = reduce(compact) or {"chip": None}
        out.update(tables=tables_summary(compact["tables"]),
                   extract_s=t1 - t0, reduce_s=time.time() - t1,
                   xplane_bytes=os.path.getsize(argv[1]))
        with open(argv[2] + ".tmp", "w") as f:
            json.dump(out, f)
        os.replace(argv[2] + ".tmp", argv[2])
        return 0
    if len(argv) not in (1, 2):
        print(__doc__, file=sys.stderr)
        return 2
    from benchmarks import trace_reduce

    xplane = trace_reduce.find_xplane(os.path.join(argv[0], "trace"))
    times = reduce(extract(xplane), int(argv[1]) if len(argv) == 2 else 10)
    print(json.dumps(times, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
