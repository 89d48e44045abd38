"""How much of the device's time the program's scopes account for: own
device time of the traced window's events that the join gives a phase and
a sub-layer of the vocabulary (``models/scopes.py``), over the device's
busy time there (``scope_times.py``). What is left outside is parameter
copies, the loops' own bookkeeping, and whatever a later change put
outside every scope. Under 90 every reader of the scopes is silent.
Percent."""

from benchmarks import scope_times


def read(record: dict):
    return scope_times.coverage_of(record)
