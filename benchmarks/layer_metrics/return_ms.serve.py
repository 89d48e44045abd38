"""Runtime, on the way out: result → planner → the client's REST poll.
Median over the window's requests of (client sees the result − guest's
last stamp)."""

from benchmarks.stats import percentile


def read(record: dict):
    gaps = [(r["seen"] - r["guest_end"]) * 1e3
            for r in record.get("requests", []) if not r.get("failed")]
    return percentile(gaps, 50) if gaps else None
