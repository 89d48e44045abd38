"""The arithmetic of the end-to-end metrics: over all the work and all the
time of the window, never over chunks or medians of gaps."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    two nearest ranks."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def request_latencies_ms(log: list) -> list:
    """Latency of every request attempted. One that failed counts as the
    slowest: it gets the longest latency seen, or its own if longer."""
    done = [(r["seen"] - r["posted"]) * 1e3 for r in log]
    worst = max(done) if done else 0.0
    return [worst if r.get("failed") else ms for r, ms in zip(log, done)]


def tokens_per_s(tokens_completed: int, window_s: float) -> float:
    if window_s <= 0:
        raise ValueError(f"a window of {window_s} s")
    return tokens_completed / window_s
