"""The whole train step's share of the gang's peak: forward and backward
operations a token needs (6 per matmul parameter, head included, embedding
gather excluded; causal attention once; recomputation not counted) times
the tokens of the window's steps, over the steps' own time (dispatch → the
blocking read of the loss; in a traced run the profiler starts and stops
between steps, and that stall is not a step's), against chips × bf16 peak.
Percent."""

from benchmarks import flops
from benchmarks.weights import sizes_of


def read(record: dict):
    steps = record.get("steps")
    if not steps or not record.get("peaks"):
        return None
    tokens = len(steps) * record["tokens_per_step"]
    spent = sum(s["end"] - s["start"] for s in steps)
    per_token = flops.train_flops_per_token(sizes_of(record["config"]),
                                            record["traffic"]["seq"])
    peak = record["cell"]["chips"] * record["peaks"]["bf16_flops_per_s"]
    return 100.0 * per_token * tokens / spent / peak
