"""What the expert layer costs a cached step: the traced own time, inside
the decode loops, of the operations that route, sort and gather the
picks, multiply by the experts held and combine
(``guests/serve_longcat.py:decode_operations``, known by kind and shape)
over the decode loops' time. The program's counters ``picks_held`` and
``experts_hit_decode`` of each request stand beside it in the record.
Percent."""

from benchmarks import trace_loops


def read(record: dict):
    found = trace_loops.traced(record)
    if not found:
        return None
    _requests, loops = found
    spent = sum(l["seconds"] for l in loops)
    if spent <= 0 or any(l.get("expert_s") is None for l in loops):
        return None
    return 100.0 * sum(l["expert_s"] for l in loops) / spent
