"""The cached-attention kernel against its roofline in a decoder-hybrid-
decoder's cached steps: the least time the chip could take for the calls
the trace holds (``cached_attention``: ``ops/cached_attention.py``, one
call an attending layer a step) over their summed device time. The least
is of the keys and values of the *written* slots, not of the allocated
ones: a window layer's ring, and the shared cache up to the step's
position once for the full layer and once for each cross layer
(``flops_phi4flash.attended_bytes`` at the traced requests' mean reach,
shared evenly over a step's calls), at the HBM peak, or the products'
operations at the bf16 peak where those take longer. Percent."""

from benchmarks import flops, flops_phi4flash, trace_loops, trace_reduce
from benchmarks.weights_phi4flash import n_params, sizes_of

KERNEL = "cached_attention"


def attention_step(sizes: dict, rows: int, context: float) -> dict:
    """All attending layers' kernel calls of one cached step:
    ``flops.least_seconds`` takes these keys."""
    layers = n_params(sizes)["layers"]
    ops = sum(layers[kind] * flops_phi4flash.attention_flops(
        sizes, flops_phi4flash.attended(sizes, kind, context))
        for kind in ("window", "full", "cross"))
    return {"flops": float(rows) * ops,
            "bytes": flops_phi4flash.attended_bytes(sizes, rows, context)}


def read(record: dict):
    found = trace_loops.traced(record)
    trace = record.get("trace")
    if not found or not trace or not record.get("peaks"):
        return None
    requests, _loops = found
    kernel = trace_reduce.kinds(trace).get(KERNEL)
    if not kernel or kernel["seconds"] <= 0 \
            or any(not r.get("attention_streamed_layers") for r in requests):
        return None
    sizes = sizes_of(record["config"])
    new = int(record["traffic"]["new_tokens"])
    context = sum(r["prompt_len"] + (new + 1) / 2.0
                  for r in requests) / len(requests)
    a_step = flops.least_seconds(
        attention_step(sizes, requests[0]["rows"], context),
        record["peaks"])["seconds"]
    calls_a_step = requests[0]["attention_streamed_layers"]
    return 100.0 * kernel["count"] * a_step / calls_a_step \
        / kernel["seconds"]
