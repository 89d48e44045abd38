"""What the latent caches cost a cached step: the traced own time, inside
the decode loops, of the operations that touch a latent cache (its
update, the scores against it, the weighted sum over it;
``guests/serve_longcat.py:decode_operations``) over the decode loops'
time. The program's counter ``cache_bytes`` of each request stands beside
it in the record. Percent."""

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
