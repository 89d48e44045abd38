"""How much of the device's time the train step's scopes account for:
own device time of the traced steps' events that the join gives the phase
``loss`` with a sub-layer, or the phase ``optimizer`` (one pass over the
leaves: it has no sub-layers), over the device's busy time there
(``scope_times.py``). Percent."""

from benchmarks import scope_times


def read(record: dict):
    return scope_times.coverage_of(record)
