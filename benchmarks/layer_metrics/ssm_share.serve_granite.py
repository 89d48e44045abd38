"""What the state-space layers cost a cached step beside their two
projections: the traced own time, inside the decode loops, of the window's
shift and product, dt and the decay, the update of S, the read-out and the
gated norm (``guests/serve_granite.py:mixer_operations``, known by kind
and result shape) over the decode loops' time. The program's counters
``state_bytes`` and ``ssm_layers`` of each request stand beside it in the
record. Percent."""

from benchmarks import trace_loops


def read(record: dict):
    found = trace_loops.traced(record)
    if not found:
        return None
    _requests, loops = found
    spent = sum(l["seconds"] for l in loops)
    if spent <= 0 or any(l.get("cache_s") is None
                         or "state_s" not in l for l in loops):
        return None
    return 100.0 * sum(l["cache_s"] for l in loops) / spent
