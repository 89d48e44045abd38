"""Tokens of all steps completed in the window over the window's time, to
the last step's blocking read of its loss."""

from benchmarks.stats import tokens_per_s


def read(record: dict):
    if "steps" not in record:
        return None
    return tokens_per_s(len(record["steps"]) * record["tokens_per_step"],
                        record["window_s"])
