"""The whole serving path's share of the chip's peak: operations the
window's completed requests need (prefill and the cached steps, from
shapes) over the requests' own time (POST → answer seen; in a traced run
the profiler starts and stops between requests, and that stall is no
request's), against the bf16 peak. Percent."""

from benchmarks import flops
from benchmarks.weights import sizes_of


def read(record: dict):
    done = [r for r in record.get("requests", []) if not r.get("failed")]
    if not done or not record.get("peaks"):
        return None
    sizes = sizes_of(record["config"])
    ops = sum(flops.request_flops(sizes, r["prompt_len"],
                                  record["new_tokens"]) for r in done)
    spent = sum(r["seen"] - r["posted"] for r in done)
    return 100.0 * ops / spent / record["peaks"]["bf16_flops_per_s"]
