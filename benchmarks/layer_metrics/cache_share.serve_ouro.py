"""What the caches cost a cached step of the looped decoder: the traced
own time, inside the decode loops, of the operations that touch a pass's
cache (its update, the scores against it, the weighted sum over it;
``trace_loops.cache_operations``) over the decode loops' time. The
program's counter ``cache_bytes`` of each request stands beside it in the
record. Percent."""

from benchmarks import trace_loops


def read(record: dict):
    found = trace_loops.traced(record)
    if not found:
        return None
    _requests, loops = found
    spent = sum(l["seconds"] for l in loops)
    if spent <= 0 or any(l["cache_s"] is None for l in loops):
        return None
    return 100.0 * sum(l["cache_s"] for l in loops) / spent
