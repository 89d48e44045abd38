"""Prefill of a bucket of rows against the bf16 peak: the operations the
traced requests' prompts need (``flops_granite.prefill_flops``: every
matrix a token, causal attention, the recurrence, the head at the last
position) over the device's busy time in each traced run before its decode
loop (``trace_loops.py``). Percent."""

from benchmarks import flops_granite, trace_loops
from benchmarks.weights_granite import sizes_of


def read(record: dict):
    found = trace_loops.traced(record)
    if not found or not record.get("peaks"):
        return None
    requests, loops = found
    spent = sum(l["before_s"] for l in loops)
    if spent <= 0 or any("state_bytes" not in r for r in requests):
        return None
    sizes = sizes_of(record["config"])
    ops = sum(flops_granite.prefill_flops(sizes, r["rows"], r["prompt_len"])
              for r in requests)
    return 100.0 * ops / spent / record["peaks"]["bf16_flops_per_s"]
