"""A trace with a loop inside the loop: the looped decoder's ``generate``
runs its passes as a rolled loop, once in prefill and once in every step of
the decode scan, so a run holds several ``while`` spans and
``decode_hbm_share.serve``'s "the one while" no longer says which is the
decode loop. This reduction finds it by nesting, over the compact form of
``trace_reduce.load_xplane`` (``[name, start_ns, dur_ns]`` a device event,
``name`` the instruction and its result shape, a ``while`` holding its
body's events inside its own span):

- the **decode loops** are the outermost ``while`` spans that hold inner
  ``while`` spans; where none does (a model of one pass), every outermost
  ``while``. A cached step's time is a decode loop's span over its steps.
- **before** a decode loop, back to the end of the one before it, lies its
  run's prefill: the busy time of the device there.
- inside the decode loops, the own time of the operations that touch a
  cache. The v5e's trace carries an instruction's name and shape and not
  its ``op_name`` (looked at by hand, my chip run, PR 27: an event's
  statistics are its offset and duration), so the program's
  ``jax.named_scope``s do not reach it, and the operations are known by
  kind and result shape (:func:`cache_operations`), as the optimized HLO
  of the decode body names them (AOT compile for a described v5e, PR 27):
  ``dynamic_update_slice`` on a layer's whole cache, the scores' fusion
  (``bhqk``: heads × slots) and the weighted sum's (heads × head size).
"""

from __future__ import annotations

import json
import os

from benchmarks import trace_reduce


def cache_operations(sizes: dict, slot_counts) -> set:
    """``"<kind> <type[shape]>"`` of the batch-1 decode body's operations
    that touch a cache whose length is one of ``slot_counts``."""
    h, hd, passes = sizes["n_heads"], sizes["head_dim"], sizes["passes"]
    found = {f"fusion bf16[{h},{hd}]"}
    for slots in slot_counts:
        found.add(f"fusion bf16[{h},{slots}]")
        found.add(f"dynamic_update_slice bf16[{passes},1,{h},{slots},{hd}]")
    return found


def _kind_and_shape(name: str) -> str:
    instruction, _, shape = name.partition(" ")
    return f"{trace_reduce.kind_of(instruction)} {shape}".strip()


def reduce_loops(compact: dict, cache_ops=None) -> dict:
    """The decode loops of the busiest chip, in time order: each with its
    span (``seconds``), the inner loops it holds, the device's busy time
    ``before_s`` it since the decode loop before, and ``cache_s``, the own
    time inside it of the operations ``cache_ops`` names (None where none
    is named)."""
    plane = max(compact["devices"],
                key=lambda p: sum(d for _n, _s, d in compact["devices"][p]))
    events = sorted(compact["devices"][plane], key=lambda e: (e[1], -e[2]))
    labels: dict = {}  # event name → (is a while, touches a cache)
    tops: list = []    # [is a while, start, end, inner whiles, cache ns]
    stack: list = []   # [end, touches a cache, own ns, index in tops]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            _end, in_cache, own, top = stack.pop()
            if in_cache:
                tops[top][4] += max(own, 0)

    for name, start, dur in events:
        if name not in labels:
            label = _kind_and_shape(name)
            labels[name] = (label.split(" ")[0] == "while",
                            bool(cache_ops) and label in cache_ops)
        is_while, in_cache = labels[name]
        close(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][0] - start)
            top = stack[-1][3]
            tops[top][3] += is_while
        else:
            top = len(tops)
            tops.append([is_while, start, start + dur, 0, 0])
        stack.append([start + dur, in_cache, dur, top])
    close(float("inf"))

    outer = [i for i, t in enumerate(tops) if t[0]]
    decode = [i for i in outer if tops[i][3] > 0] or outer
    loops = []
    since = 0
    for i in decode:
        before = trace_reduce._union([[t[1], t[2]] for t in tops[since:i]])
        loops.append({
            "start_ns": tops[i][1],
            "seconds": (tops[i][2] - tops[i][1]) / 1e9,
            "inner_loops": tops[i][3],
            "before_s": sum(e - s for s, e in before) / 1e9,
            "cache_s": tops[i][4] / 1e9 if cache_ops else None,
        })
        since = i + 1
    return {"chip": plane, "outermost_whiles": len(outer),
            "decode_loops": loops}


def reduce_to_files(trace_dir: str, out_dir: str, cache_ops=None) -> dict:
    """What the guest does once the profiler has stopped: one read of the
    trace, ``trace_reduce``'s reduction left where ``reduce_to_file`` leaves
    it, and this file's beside it."""
    compact = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
    planes = compact.pop("planes")
    path = loops_path = None
    if any(compact["devices"].values()):
        path = os.path.join(out_dir, "trace_reduced.json")
        with open(path, "w") as f:
            json.dump(trace_reduce.reduce(compact), f)
        loops_path = os.path.join(out_dir, "trace_loops.json")
        with open(loops_path, "w") as f:
            json.dump(reduce_loops(compact, cache_ops), f)
    return {"trace_file": path, "loops_file": loops_path, "planes": planes}


def traced(record: dict):
    """(the traced requests, their decode loops), paired in order; nothing
    where the run was not traced or the trace does not hold one decode
    loop a traced request."""
    loops = (record.get("trace_loops") or {}).get("decode_loops")
    if not loops or "requests" not in record:
        return None
    trace = record["traffic"]["trace"]
    skip = int(trace["skip_requests"])
    requests = [r for r in record["requests"][skip:skip + int(
        trace["requests"])] if not r.get("failed")]
    if len(requests) != len(loops):
        return None
    return requests, loops
