"""The whole serving path's share of the chip's peak, for the looped
decoder: operations the window's completed requests need (prefill and the
cached steps; every block matrix once a pass a token, the head once,
attention once a pass a layer; ``flops_ouro.py``) over the requests' own
time (POST → answer seen), against the bf16 peak. Percent."""

from benchmarks import flops_ouro
from benchmarks.weights_ouro import sizes_of


def read(record: dict):
    done = [r for r in record.get("requests", []) if not r.get("failed")]
    if not done or not record.get("peaks"):
        return None
    sizes = sizes_of(record["config"])
    ops = sum(flops_ouro.request_flops(sizes, r["prompt_len"],
                                       record["new_tokens"]) for r in done)
    spent = sum(r["seen"] - r["posted"] for r in done)
    return 100.0 * ops / spent / record["peaks"]["bf16_flops_per_s"]
