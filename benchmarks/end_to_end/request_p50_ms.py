"""Median over all requests of the window, client POST → result seen."""

from benchmarks.stats import percentile, request_latencies_ms


def read(record: dict):
    if "requests" not in record:
        return None
    return percentile(request_latencies_ms(record["requests"]), 50)
