"""Cached decoding at batch 64 against the memory roofline: the bytes one
cached step must read (every matrix outside the experts and the head's
slice once, the routed experts that got at least one token in the step,
the latent caches over the positions attended;
``flops_longcat.decode_step_bytes``) at the HBM peak, over the traced time
of a cached step. The experts a step hit are the program's own counter
``experts_hit_decode`` of the traced requests over their steps: a program
that streams experts nobody picked reads lower. The step time is the span
of a traced run's decode ``while`` (``trace_loops.py``, the short loops of
prefill folded away by the guest) over the steps it ran. Percent."""

from benchmarks import flops_longcat, trace_loops
from benchmarks.weights_longcat import sizes_of


def read(record: dict):
    found = trace_loops.traced(record)
    if not found or not record.get("peaks"):
        return None
    requests, loops = found
    if any("experts_hit_decode" not in r for r in requests):
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
    need = flops_longcat.decode_step_bytes(
        sizes_of(record["config"]), requests[0]["rows"], context, hit)
    return 100.0 * need / record["peaks"]["hbm_bytes_per_s"] / step_s
