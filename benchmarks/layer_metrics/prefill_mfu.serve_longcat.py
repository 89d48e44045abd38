"""Prefill of a bucket of rows against the bf16 peak: the operations the
traced requests' prompts need (``flops_longcat.prefill_flops``, and the
experts held for the share of the call's held picks that prefill's
positions are) over the device's busy time in each traced run before its
decode loop (``trace_loops.py``). Percent."""

from benchmarks import flops_longcat, trace_loops
from benchmarks.weights_longcat import sizes_of


def read(record: dict):
    found = trace_loops.traced(record)
    if not found or not record.get("peaks"):
        return None
    requests, loops = found
    spent = sum(l["before_s"] for l in loops)
    if spent <= 0 or any("picks_held" not in r for r in requests):
        return None
    sizes = sizes_of(record["config"])
    new = int(record["traffic"]["new_tokens"])
    ops = sum(flops_longcat.prefill_flops(sizes, r["rows"], r["prompt_len"])
              + flops_longcat.expert_flops(
                  sizes, r["picks_held"] * r["prompt_len"]
                  / (r["prompt_len"] + new))
              for r in requests)
    return 100.0 * ops / spent / record["peaks"]["bf16_flops_per_s"]
