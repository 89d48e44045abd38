"""Cached decoding at batch 64 against the memory roofline: the bytes one
cached step must move (every matrix and the table once, the recurrent
states read and written, the convolution windows, the keys and values of
the positions attended; ``flops_granite.decode_step_bytes``) at the HBM
peak, over the traced time of a cached step. The step time is the span of
a traced run's decode ``while`` (``trace_loops.py``: the program's one
loop) over the steps it ran. Percent."""

from benchmarks import flops_granite, trace_loops
from benchmarks.weights_granite import sizes_of


def read(record: dict):
    found = trace_loops.traced(record)
    if not found or not record.get("peaks"):
        return None
    requests, loops = found
    if any("state_bytes" not in r for r in requests):
        return None
    new = int(record["traffic"]["new_tokens"])
    step_s = sum(l["seconds"] for l in loops) / (len(loops) * new)
    if step_s <= 0:
        return None
    # the mean over the traced requests' cached steps
    context = sum(r["prompt_len"] + (new + 1) / 2.0
                  for r in requests) / len(requests)
    need = flops_granite.decode_step_bytes(
        sizes_of(record["config"]), requests[0]["rows"], context)
    return 100.0 * need / record["peaks"]["hbm_bytes_per_s"] / step_s
