"""Cached decoding at batch 64 against the memory roofline: the least
bytes one cached step must move (every matrix and the table once, the
Mamba-1 states read and written, the convolution windows, the keys and
values of the written slots of the window layers' rings, and the one
shared cache once for the full layer and once for each cross layer;
``flops_phi4flash.decode_step_bytes``) at the HBM peak, over the traced
time of a cached step: the span of a traced run's decode loop
(``guests/serve_phi4flash.py:decode_loops``) over the steps it ran.
Percent."""

from benchmarks import flops_phi4flash, trace_loops
from benchmarks.weights_phi4flash import sizes_of


def read(record: dict):
    found = trace_loops.traced(record)
    if not found or not record.get("peaks"):
        return None
    requests, loops = found
    if any("window_slots" not in r for r in requests):
        return None
    new = int(record["traffic"]["new_tokens"])
    step_s = sum(l["seconds"] for l in loops) / (len(loops) * new)
    if step_s <= 0:
        return None
    # the mean over the traced requests' cached steps, the new position
    # counted (bytes are linear in it beyond the window)
    context = sum(r["prompt_len"] + (new + 1) / 2.0
                  for r in requests) / len(requests)
    need = flops_phi4flash.decode_step_bytes(
        sizes_of(record["config"]), requests[0]["rows"], context)
    return 100.0 * need / record["peaks"]["hbm_bytes_per_s"] / step_s
