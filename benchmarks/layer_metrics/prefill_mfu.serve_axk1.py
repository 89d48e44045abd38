"""Prefill of a bucket of long rows against the bf16 peak: the operations
the traced requests' prompts need (``flops_axk1.prefill_flops``: every
matrix outside the routed experts a position, causal attention on
expanded keys and values counted once whatever the chunks, the head at
the last position; and the experts held for the share of the call's held
picks that prefill's positions are) over the device's busy time in each
traced run before its decode loop (``guests/serve_axk1.py``: the long
``while``, prefill's short loops folded into what lies before it).
Percent."""

from benchmarks import flops_axk1, trace_loops
from benchmarks.weights_axk1 import sizes_of


def read(record: dict):
    found = trace_loops.traced(record)
    if not found or not record.get("peaks"):
        return None
    requests, loops = found
    spent = sum(l["before_s"] for l in loops)
    if spent <= 0 or any("picks_held" not in r or "shared_experts" not in r
                         for r in requests):
        return None
    sizes = sizes_of(record["config"])
    new = int(record["traffic"]["new_tokens"])
    ops = sum(flops_axk1.prefill_flops(sizes, r["rows"], r["prompt_len"])
              + flops_axk1.expert_flops(
                  sizes, r["picks_held"] * r["prompt_len"]
                  / (r["prompt_len"] + new))
              for r in requests)
    return 100.0 * ops / spent / record["peaks"]["bf16_flops_per_s"]
