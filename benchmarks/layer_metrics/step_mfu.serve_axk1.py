"""The whole serving path's share of the chip's peak, for questions over
long documents through latent attention, a dense layer and expert layers
with a shared expert: operations the window's completed requests need
(prefill in whatever chunks, causal attention counted once and no
re-expansion counted, and the cached steps of every row; the routed
experts held by the picks that fell on them, which the program counted on
the device; ``flops_axk1.py``) over the requests' own time (POST → all
answers seen), against the bf16 peak. Percent."""

from benchmarks import flops_axk1
from benchmarks.weights_axk1 import sizes_of


def read(record: dict):
    done = [r for r in record.get("requests", [])
            if not r.get("failed") and "picks_held" in r
            and "shared_experts" in r]
    if not done or not record.get("peaks"):
        return None
    sizes = sizes_of(record["config"])
    new = int(record["traffic"]["new_tokens"])
    ops = sum(flops_axk1.request_flops(
        sizes, r["rows"], r["prompt_len"], new, r["picks_held"])
        for r in done)
    spent = sum(r["seen"] - r["posted"] for r in done)
    return 100.0 * ops / spent / record["peaks"]["bf16_flops_per_s"]
