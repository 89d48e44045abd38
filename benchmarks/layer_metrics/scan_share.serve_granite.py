"""What the chunked form costs prefill: the traced time, before each
traced run's decode loop, of the state-space layers' operations between
their two projections (the convolution, the decays' cumulative sums, C·Bᵀ,
the masked product, the carried state's part and its update;
``guests/serve_granite.py:mixer_operations``) over the device's busy time
there. The program's counter ``scan_chunks`` of each request stands
beside it in the record. Percent."""

from benchmarks import trace_loops


def read(record: dict):
    found = trace_loops.traced(record)
    if not found:
        return None
    _requests, loops = found
    spent = sum(l["before_s"] for l in loops)
    if spent <= 0 or any(l.get("scan_s") is None for l in loops):
        return None
    return 100.0 * sum(l["scan_s"] for l in loops) / spent
