"""Shared by the kernels' roofline readers: the shapes one call sees on one
chip of the gang, and the share of the roofline itself."""

from benchmarks import flops, trace_reduce
from benchmarks.weights import sizes_of


def local_call_shape(record: dict) -> tuple:
    sizes, traffic = sizes_of(record["config"]), record["traffic"]
    tp = int(traffic["tp"])
    dp = record["cell"]["chips"] // tp
    return (int(traffic["batch"]) // dp, int(traffic["seq"]),
            sizes["n_heads"] // tp, sizes["head_dim"])


def roofline_share(record: dict, kernels: dict):
    """``kernels``: trace name → function of the call's shape giving
    operations and bytes. Least time of every call found over their summed
    device time, on the busiest chip; nothing where no call is found."""
    trace = record.get("trace")
    if not trace or not record.get("peaks") or "steps" not in record:
        return None
    found = trace_reduce.kinds(trace)
    shape = local_call_shape(record)
    least = spent = 0.0
    for name, call in kernels.items():
        if name in found:
            least += found[name]["count"] * flops.least_seconds(
                call(*shape), record["peaks"])["seconds"]
            spent += found[name]["seconds"]
    return 100.0 * least / spent if spent > 0 else None
