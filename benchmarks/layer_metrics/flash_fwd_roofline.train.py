"""``flash_fwd`` against its roofline: the least time the peaks allow for
every call in the trace (operations and bytes from the call's shape; it is
compute-bound at these sizes) over the calls' summed device time.
Percent."""

from benchmarks import flops, rooflines


def read(record: dict):
    return rooflines.roofline_share(record, {"flash_fwd": flops.flash_fwd_call})
