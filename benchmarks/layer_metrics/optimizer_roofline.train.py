"""AdamW against its roofline: the least time the peaks allow one update
over the float32 leaves (``scope_times.adamw_call``: parameter, gradient
and both moments read, parameter and both moments written, 28 bytes a
parameter of the configuration; memory-bound) times the traced steps,
over the device time of the events charged to the ``optimizer`` scope and
of the fusions that touch it: ``optimizer_share.train``'s time. Where XLA
fuses a leaf's weight-gradient product into its update that time holds
the product too, and the share says how far the fused pass is from the
pass over the leaves alone. Percent."""

from benchmarks import flops, scope_times
from benchmarks.weights import n_params, sizes_of


def read(record: dict):
    times = scope_times.of_record(record)
    if not times or not record.get("peaks"):
        return None
    spent = scope_times.touching_s(times, "optimizer")
    call = scope_times.adamw_call(n_params(sizes_of(record["config"]))["total"])
    least = flops.least_seconds(call, record["peaks"])["seconds"]
    return 100.0 * times["host_spans"] * least / spent if spent > 0 else None
