"""The tail as far as a window shows it: 90th percentile over all requests
of the window, client POST → result seen; a failed request counts as the
slowest. Not an end-to-end metric with a bound: a window holds 57 requests,
so five or six lie beyond it (PERF.md section 2)."""

from benchmarks.stats import percentile, request_latencies_ms


def read(record: dict):
    if "requests" not in record:
        return None
    return percentile(request_latencies_ms(record["requests"]), 90)
