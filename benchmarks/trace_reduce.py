"""From the profiler's trace to numbers: device busy and idle time, time
by operation name, and the longest idle gaps by what the host was doing.

Two steps, so that the arithmetic can be tested without a profiler:

- :func:`load_xplane` reads an ``.xplane.pb`` with
  ``jax.profiler.ProfileData`` into a plain dict (``compact`` form):
  ``{"devices": {plane: [[name, start_ns, dur_ns], ...]},
  "host": [[name, start_ns, dur_ns], ...]}``. Device events are those of
  each device plane's operation line, named ``<instruction> <type[shape]>``;
  host events are the benchmark's own ``TraceAnnotation`` spans (names
  starting with ``bench:``).
- :func:`reduce` works on that dict alone.

What a v5e trace looks like (looked at by hand, my chip run, PR 24): one
plane per chip named ``/device:TPU:<n>`` with the lines ``Scalar Unit``,
``XLA Modules`` (one event per run of a jitted program), ``XLA Ops``,
``Async XLA Ops`` and ``TC Overlay``; host threads are lines of the plane
``/host:CPU``. An ``XLA Ops`` event is one run of one HLO instruction and
is named by the instruction's whole text, ``%fusion.3200 =
bf16[2048,16]{...} fusion(...)``; a ``while`` holds its body's events
inside its own span. A Pallas kernel is a custom call named after the
kernel: ``%rms_norm.49 = ... custom_call_target="tpu_custom_call"``. So an
event is kept under its instruction name (``fusion.3200``), and grouped by
*kind*: that name without its number (``fusion``, ``rms_norm``, ``while``).
"""

from __future__ import annotations

import glob
import json
import os
import re

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_SPAN_PREFIX = "bench:"


_INSTRUCTION = re.compile(r"%?([^\s=]+)(?: = \(?(\w+\[[\d,]*\]))?")
_NUMBER = re.compile(r"(\.\d+|\.clone|\.remat\d*)+$")


def instruction_of(event_name: str) -> tuple:
    """(instruction name, result type and shape or "") of an ``XLA Ops``
    event: ``%fusion.3200 = bf16[2048,16]{1,0} fusion(...)`` →
    (``fusion.3200``, ``bf16[2048,16]``)."""
    m = _INSTRUCTION.match(event_name)
    return (m.group(1), m.group(2) or "") if m else (event_name, "")


def kind_of(instruction: str) -> str:
    """``fusion.3200`` → ``fusion``; ``flash_fwd.7.clone`` → ``flash_fwd``."""
    return _NUMBER.sub("", instruction) or instruction


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    compact: dict = {"devices": {}, "host": [], "planes": {}}
    for plane in data.planes:
        lines = list(plane.lines)
        compact["planes"][plane.name] = [line.name for line in lines]
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in lines:
                if line.name == OPS_LINE:
                    compact["devices"][plane.name] = [
                        [" ".join(instruction_of(e.name)).strip(),
                         int(e.start_ns), int(e.duration_ns)]
                        for e in line.events]
        else:
            for line in lines:
                for e in line.events:
                    if e.name.startswith(HOST_SPAN_PREFIX):
                        compact["host"].append(
                            [e.name, int(e.start_ns), int(e.duration_ns)])
    compact["host"].sort(key=lambda e: e[1])
    return compact


def reduce_to_file(trace_dir: str, out_dir: str) -> dict:
    """What a guest does once the profiler has stopped: read the trace and
    leave the reduced form beside the logs (it is too long for a reply
    through the planner). ``trace_file`` is None where the trace holds no
    device plane, as in a rehearsal on the CPU."""
    compact = load_xplane(find_xplane(trace_dir))
    planes = compact.pop("planes")
    path = None
    if any(compact["devices"].values()):
        path = os.path.join(out_dir, "trace_reduced.json")
        with open(path, "w") as f:
            json.dump(reduce(compact), f)
    return {"trace_file": path, "planes": planes}


def load_reduced(path):
    """The parent's half of :func:`reduce_to_file`."""
    if not path:
        return None
    with open(path) as f:
        return json.load(f)


def _union(intervals: list) -> list:
    """Merged [start, end) intervals, sorted."""
    merged: list = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def self_times(events: list) -> dict:
    """Seconds by operation name, each event counted for its own span less
    what events nested inside it cover (a ``while`` and its body are not
    counted twice)."""
    out: dict = {}
    stack: list = []  # [name, end, remaining_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _end, own = stack.pop()
            out[name] = out.get(name, 0.0) + max(own, 0) / 1e9

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return out


def _label_gap(start: int, end: int, spans: list) -> str:
    """What the host was doing in [start, end): the benchmark span that
    holds it, or the two it falls between."""
    before = after = None
    for name, s, d in spans:
        if s <= start and end <= s + d:
            return f"inside {name}"
        if s + d <= start:
            before = name
        elif s >= end and after is None:
            after = name
    if before and after:
        return f"between {before} and {after}"
    if before:
        return f"after {before}"
    if after:
        return f"before {after}"
    return "outside any span"


def _strip(name: str) -> str:
    """A span's name without its number: gaps of a kind add up."""
    head, _, tail = name.rpartition("#")
    return head if head and tail.isdigit() else name


def window_of(compact: dict) -> tuple:
    """The traced window: from the first benchmark span's start to the last
    one's end where the host recorded spans, else the device events'
    extent."""
    if compact["host"]:
        return (min(s for _n, s, _d in compact["host"]),
                max(s + d for _n, s, d in compact["host"]))
    starts = [s for ev in compact["devices"].values() for _n, s, _d in ev]
    ends = [s + d for ev in compact["devices"].values() for _n, s, d in ev]
    if not starts:
        raise ValueError("the trace holds no device operation")
    return min(starts), max(ends)


def _by_kind(events: list) -> dict:
    """kind → {"count", "seconds" (whole spans), "own_seconds" (nested
    events taken out)} and the same by kind and shape, for the breakdown."""
    own = self_times(events)
    kinds: dict = {}
    shaped: dict = {}
    for name, _s, dur in events:
        instruction, _, shape = name.partition(" ")
        kind = kind_of(instruction)
        k = kinds.setdefault(kind, {"count": 0, "seconds": 0.0,
                                    "own_seconds": 0.0})
        k["count"] += 1
        k["seconds"] += dur / 1e9
    for name, seconds in own.items():
        instruction, _, shape = name.partition(" ")
        kind = kind_of(instruction)
        kinds[kind]["own_seconds"] += seconds
        label = f"{kind} {shape}".strip()
        shaped[label] = shaped.get(label, 0.0) + seconds
    return {"kinds": kinds, "shaped": shaped}


def reduce(compact: dict, top: int = 10) -> dict:
    """Busy seconds (mean over the chips, and by chip), the window, time
    by kind of operation on every chip, the operations that took most time
    (own time, by kind and result shape, on the busiest chip) and the idle
    gaps by what the host was doing."""
    if not any(compact["devices"].values()):
        raise ValueError("the trace holds no device operation")
    w0, w1 = window_of(compact)
    busy_by_chip, kinds_by_chip, shaped_by_chip, gaps_by_chip = {}, {}, {}, {}
    for plane, events in compact["devices"].items():
        inside = [[n, max(s, w0), min(s + d, w1) - max(s, w0)]
                  for n, s, d in events if s < w1 and s + d > w0]
        merged = _union([[s, s + d] for _n, s, d in inside])
        busy_by_chip[plane] = sum(e - s for s, e in merged) / 1e9
        grouped = _by_kind(inside)
        kinds_by_chip[plane] = grouped["kinds"]
        shaped_by_chip[plane] = grouped["shaped"]
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        gaps_by_chip[plane] = [(edges[i], edges[i + 1])
                               for i in range(0, len(edges), 2)
                               if edges[i + 1] > edges[i]]
    busiest = max(busy_by_chip, key=busy_by_chip.get)
    by_label: dict = {}
    spans = [[_strip(n), s, d] for n, s, d in compact["host"]]
    for start, end in gaps_by_chip[busiest]:
        label = _label_gap(start, end, spans)
        by_label[label] = by_label.get(label, 0.0) + (end - start) / 1e9

    def ranked(d):
        return sorted(([n, s] for n, s in d.items()),
                      key=lambda kv: -kv[1])[:top]

    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy_by_chip.values()) / len(busy_by_chip),
        "busy_s_by_chip": busy_by_chip,
        "busiest_chip": busiest,
        "kinds_by_chip": kinds_by_chip,
        "device_ops": ranked(shaped_by_chip[busiest]),
        "idle_gaps": ranked(by_label),
        "host_spans": [[n, s - w0, d] for n, s, d in compact["host"]],
    }


def kinds(reduced: dict, chip: str | None = None) -> dict:
    """The busiest chip's (or one chip's) operations by kind."""
    return reduced["kinds_by_chip"][chip or reduced["busiest_chip"]]
