"""Cached decoding of the looped decoder against the memory roofline: the
bytes one cached step must read (every block matrix once a pass, head and
gate once, the keys and values of every pass's cache over the positions it
attends; ``flops_ouro.decode_step_bytes``) at the HBM peak, over the traced
time of a cached step. The step time is the span of a run's outermost
``while`` that holds the pass loops (``trace_loops.py``) over the steps it
ran; the context is the traced requests' own. Percent."""

from benchmarks import flops_ouro, trace_loops
from benchmarks.weights_ouro import sizes_of


def read(record: dict):
    found = trace_loops.traced(record)
    if not found or not record.get("peaks"):
        return None
    requests, loops = found
    new = record["new_tokens"]
    step_s = sum(l["seconds"] for l in loops) / (len(loops) * new)
    if step_s <= 0:
        return None
    # the mean context of a cached step over the traced requests
    context = sum(r["prompt_len"] + (new + 1) / 2.0
                  for r in requests) / len(requests)
    need = flops_ouro.decode_step_bytes(sizes_of(record["config"]), context)
    return 100.0 * need / record["peaks"]["hbm_bytes_per_s"] / step_s
