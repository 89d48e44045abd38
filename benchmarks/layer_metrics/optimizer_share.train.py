"""What the optimizer costs a train step: own device time of the events
charged to the program's ``optimizer`` scope and of the fusions that touch
it (XLA fuses a leaf's weight-gradient product with its AdamW update: the
fusion is charged to its root and keeps the scopes of what was fused into
it) over the traced steps' busy time (``scope_times.py``). Percent."""

from benchmarks import scope_times


def read(record: dict):
    times = scope_times.of_record(record)
    if not times:
        return None
    return 100.0 * scope_times.touching_s(times, "optimizer") \
        / times["busy_s"]
