"""The update and read-out of the recurrent state against its roofline:
the least time the chip could take for them in the traced cached steps
(every state-space layer's S read and written once a step at the HBM
peak, their operations at the bf16 peak: ``flops_granite.state_step``;
memory-bound) over the traced own time, inside the decode loops, of the
operations that touch S (``guests/serve_granite.py:mixer_operations``:
counted from shapes, so it reads the same work whether XLA's fusions or a
kernel do it). Percent."""

from benchmarks import flops, flops_granite, trace_loops
from benchmarks.weights_granite import sizes_of


def read(record: dict):
    found = trace_loops.traced(record)
    if not found or not record.get("peaks"):
        return None
    requests, loops = found
    spent = sum(l.get("state_s") or 0.0 for l in loops)
    if spent <= 0:
        return None
    steps = len(loops) * int(record["traffic"]["new_tokens"])
    least = flops.least_seconds(flops_granite.state_step(
        sizes_of(record["config"]), requests[0]["rows"]), record["peaks"])
    return 100.0 * steps * least["seconds"] / spent
