"""The whole serving path's share of the chip's peak, for batched requests
through a decoder-hybrid-decoder: operations the window's completed
requests need (prefill with the cross-decoder at the last position alone,
and the cached steps of every row; ``flops_phi4flash.py``) over the
requests' own time (POST → all answers seen), against the bf16 peak.
Percent."""

from benchmarks import flops_phi4flash
from benchmarks.weights_phi4flash import sizes_of


def read(record: dict):
    done = [r for r in record.get("requests", [])
            if not r.get("failed") and "window_slots" in r]
    if not done or not record.get("peaks"):
        return None
    sizes = sizes_of(record["config"])
    new = int(record["traffic"]["new_tokens"])
    ops = sum(flops_phi4flash.request_flops(
        sizes, r["rows"], r["prompt_len"], new) for r in done)
    spent = sum(r["seen"] - r["posted"] for r in done)
    return 100.0 * ops / spent / record["peaks"]["bf16_flops_per_s"]
