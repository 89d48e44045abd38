"""The whole serving path's share of the chip's peak, for batched requests
through state-space and grouped-query attention layers: operations the
window's completed requests need (prefill and the cached steps of every
row, the recurrence counted too; ``flops_granite.py``) over the requests'
own time (POST → all answers seen), against the bf16 peak. Percent."""

from benchmarks import flops_granite
from benchmarks.weights_granite import sizes_of


def read(record: dict):
    done = [r for r in record.get("requests", [])
            if not r.get("failed") and "state_bytes" in r]
    if not done or not record.get("peaks"):
        return None
    sizes = sizes_of(record["config"])
    new = int(record["traffic"]["new_tokens"])
    ops = sum(flops_granite.request_flops(sizes, r["rows"], r["prompt_len"],
                                          new) for r in done)
    spent = sum(r["seen"] - r["posted"] for r in done)
    return 100.0 * ops / spent / record["peaks"]["bf16_flops_per_s"]
