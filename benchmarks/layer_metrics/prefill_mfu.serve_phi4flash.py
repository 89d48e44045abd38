"""Prefill of a bucket of rows against the bf16 peak: the operations the
traced requests' prompts need with the skip (``flops_phi4flash.
prefill_flops``: the self-decoder at every position, the cross-decoder,
final norm and head at the last alone; not the whole stack's) over the
device's busy time in each traced run before its decode loop
(``guests/serve_phi4flash.py:decode_loops``). Percent."""

from benchmarks import flops_phi4flash, trace_loops
from benchmarks.weights_phi4flash import sizes_of


def read(record: dict):
    found = trace_loops.traced(record)
    if not found or not record.get("peaks"):
        return None
    requests, loops = found
    spent = sum(l["before_s"] for l in loops)
    if spent <= 0 or any("window_slots" not in r for r in requests):
        return None
    sizes = sizes_of(record["config"])
    ops = sum(flops_phi4flash.prefill_flops(sizes, r["rows"],
                                            r["prompt_len"])
              for r in requests)
    return 100.0 * ops / spent / record["peaks"]["bf16_flops_per_s"]
