"""``flash_bwd_dq`` and ``flash_bwd_dkv`` together against their roofline:
least time of every call of either in the trace over their summed device
time. Percent."""

from benchmarks import flops, rooflines


def read(record: dict):
    return rooflines.roofline_share(record, {
        "flash_bwd_dq": flops.flash_bwd_dq_call,
        "flash_bwd_dkv": flops.flash_bwd_dkv_call})
