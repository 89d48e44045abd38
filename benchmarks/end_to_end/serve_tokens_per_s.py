"""Generated tokens of all requests completed in the window over the
window (its start → the last answer seen)."""

from benchmarks.stats import tokens_per_s


def read(record: dict):
    if "requests" not in record:
        return None
    done = sum(1 for r in record["requests"] if not r.get("failed"))
    return tokens_per_s(done * record["new_tokens"], record["window_s"])
