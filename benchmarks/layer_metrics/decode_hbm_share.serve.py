"""Cached decoding against the memory roofline: the bytes one cached step
must read (every matmul weight once in bf16, and the keys and values of
the positions it attends) at the HBM peak, over the traced time of a
cached step. The step time is the device time of the decode loop (the
``while`` of ``generate``'s scan, its body included) over the steps it
ran. Percent."""

from benchmarks import flops, trace_reduce
from benchmarks.weights import sizes_of


def read(record: dict):
    trace = record.get("trace")
    if not trace or not record.get("peaks"):
        return None
    loop = trace_reduce.kinds(trace).get("while")
    if not loop or loop["seconds"] <= 0:
        return None
    runs, seconds = loop["count"], loop["seconds"]
    new = record["new_tokens"]
    step_s = seconds / (runs * new)
    sizes = sizes_of(record["config"])
    done = [r for r in record["requests"] if not r.get("failed")]
    # the mean context of a cached step over the window's mix
    contexts = [r["prompt_len"] + (new + 1) / 2.0 for r in done]
    need = flops.decode_step_bytes(sizes, sum(contexts) / len(contexts))
    return 100.0 * need / record["peaks"]["hbm_bytes_per_s"] / step_s
