"""Cached decoding at batch 8 and a long reach against the memory
roofline: the least bytes one cached step must read (every layer outside
its routed experts and the head's slice once, the routed experts that got
at least one token in the step, the latent caches over the positions
attended at the traced requests' mean reach;
``flops_axk1.decode_step_bytes``) at the HBM peak, over the traced time
of a cached step. The experts a step hit are the program's own counter
``experts_hit_decode`` of the traced requests over their steps: a program
that streams experts nobody picked reads lower. The step time is the span
of a traced run's decode loop (``guests/serve_axk1.py``: the long
``while`` of 64 steps) over the steps it ran. Percent."""

from benchmarks import flops_axk1, trace_loops
from benchmarks.weights_axk1 import sizes_of


def read(record: dict):
    found = trace_loops.traced(record)
    if not found or not record.get("peaks"):
        return None
    requests, loops = found
    if any("experts_hit_decode" not in r or "shared_experts" not in r
           for r in requests):
        return None
    new = int(record["traffic"]["new_tokens"])
    step_s = sum(l["seconds"] for l in loops) / (len(loops) * new)
    if step_s <= 0:
        return None
    # means over the traced requests' cached steps
    context = sum(r["prompt_len"] + (new + 1) / 2.0
                  for r in requests) / len(requests)
    hit = sum(r["experts_hit_decode"] for r in requests) / (
        len(requests) * new)
    need = flops_axk1.decode_step_bytes(
        sizes_of(record["config"]), requests[0]["rows"], context, hit)
    return 100.0 * need / record["peaks"]["hbm_bytes_per_s"] / step_s
