"""Runtime, on the way in: REST ingress → planner decision → dispatch →
executor queue. Median over the window's requests of (guest's first stamp −
client's POST stamp), both ``time.time()`` on one machine."""

from benchmarks.stats import percentile


def read(record: dict):
    gaps = [(r["guest_start"] - r["posted"]) * 1e3
            for r in record.get("requests", []) if not r.get("failed")]
    return percentile(gaps, 50) if gaps else None
