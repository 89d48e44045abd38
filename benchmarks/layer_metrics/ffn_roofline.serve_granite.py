"""The feed-forwards of the cached steps against their roofline: the least
time the chip could take for them in the traced cached steps (every
layer's three matrices read once a step at the HBM peak, their products
at the bf16 peak: :func:`feed_forward_call`; memory-bound at 64 rows) over
the traced own time of the operations that do that work, whoever does it:

- the streaming kernel's calls, by its name among the trace's operations
  by kind (``gated_ffn``: ``ops/gated_ffn.py``), as
  ``rooflines.roofline_share`` finds ``flash_fwd``: the calls found times
  the least time of one over their summed device time;
- where the trace holds no such call, XLA's two fusions, by kind and
  result shape among the trace's operations that took most time:
  ``fusion bf16[rows,d_ff]`` (the gate product with its silu) and
  ``multiply_add_fusion bf16[rows,d_model]`` (the down product, the up
  product held as its producer, the residual), over the layers and the
  traced steps. Two-dimensional only in a cached step: prefill's results
  carry the chunk's positions. Nothing where either is missing from the
  ten operations the reduced trace keeps;
- with either, the waits for the pieces of a gate or up matrix that XLA
  copied into VMEM ahead of the operation that reads it (``slice-done``
  and ``copy-done`` of ``bf16[r,d_ff]``, r a divisor of d_model: no other
  matrix of the program has d_ff columns), where they are among those
  ten: the copy itself runs under other operations, the wait is the
  feed-forward's. A piece of the down matrix, ``bf16[r,d_model]``, cannot
  be told from one of the mixer's out-projection and is not counted.

Percent."""

import re

from benchmarks import flops, trace_loops, trace_reduce
from benchmarks.weights_granite import sizes_of

KERNEL = "gated_ffn"
_PIECE = re.compile(r"(?:slice|copy)-done bf16\[(\d+),(\d+)\]")


def feed_forward_call(sizes: dict, rows: int) -> dict:
    """One layer's gated feed-forward on ``rows`` rows of one position:
    three products of 2 operations a parameter a row, and the three
    matrices in bfloat16 (the rows in and out are a 200th of them and not
    counted); ``flops.least_seconds`` takes these keys."""
    matrices = 3 * sizes["d_model"] * sizes["d_ff"]
    return {"flops": 2.0 * rows * matrices, "bytes": 2.0 * matrices}


def waits_for_pieces(device_ops, sizes: dict) -> float:
    """Seconds waited for copies of pieces of a gate or up matrix."""
    waited = 0.0
    for label, seconds in device_ops:
        piece = _PIECE.fullmatch(label)
        if piece and int(piece.group(2)) == sizes["d_ff"] \
                and sizes["d_model"] % int(piece.group(1)) == 0:
            waited += seconds
    return waited


def read(record: dict):
    found = trace_loops.traced(record)
    trace = record.get("trace")
    if not found or not trace or not record.get("peaks"):
        return None
    requests, loops = found
    sizes, rows = sizes_of(record["config"]), int(requests[0]["rows"])
    least = flops.least_seconds(feed_forward_call(sizes, rows),
                                record["peaks"])["seconds"]
    kernel = trace_reduce.kinds(trace).get(KERNEL)
    if kernel:
        calls, spent = kernel["count"], kernel["seconds"]
    else:
        took = dict(trace["device_ops"])
        fusions = [took.get(f"fusion bf16[{rows},{sizes['d_ff']}]"),
                   took.get(f"multiply_add_fusion "
                            f"bf16[{rows},{sizes['d_model']}]")]
        if None in fusions:
            return None
        calls = (sizes["n_layers"] * len(loops)
                 * int(record["traffic"]["new_tokens"]))
        spent = sum(fusions)
    spent += waits_for_pieces(trace["device_ops"], sizes)
    return 100.0 * calls * least / spent if spent > 0 else None
