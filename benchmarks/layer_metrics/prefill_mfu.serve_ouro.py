"""Prefill of the looped decoder against the bf16 peak: the operations the
traced requests' prompts need (``flops_ouro.prefill_flops``) over the
device's busy time in each traced run before its outermost ``while`` (the
decode loop; ``trace_loops.py``). Percent."""

from benchmarks import flops_ouro, trace_loops
from benchmarks.weights_ouro import sizes_of


def read(record: dict):
    found = trace_loops.traced(record)
    if not found or not record.get("peaks"):
        return None
    requests, loops = found
    spent = sum(l["before_s"] for l in loops)
    if spent <= 0:
        return None
    sizes = sizes_of(record["config"])
    ops = sum(flops_ouro.prefill_flops(sizes, r["prompt_len"])
              for r in requests)
    return 100.0 * ops / spent / record["peaks"]["bf16_flops_per_s"]
