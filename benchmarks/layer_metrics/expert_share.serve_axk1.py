"""What the expert layers cost the traced requests: own device time under
``router`` and ``experts`` (the sigmoid router and its selection, the sort
of the picks, the loop over the row tiles of the experts held, the shared
expert, the join) in both phases, ``prefill`` and ``decode_step``, over
the device's busy time in the traced requests (``scope_times.py``).
Percent."""

from benchmarks import scope_times

SUBLAYERS = ("router", "experts")


def read(record: dict):
    times = scope_times.of_record(record)
    if not times or times["busy_s"] <= 0:
        return None
    spent = sum(scope_times.phase_s(times, phase, SUBLAYERS)
                for phase in ("prefill", "decode_step"))
    return 100.0 * spent / times["busy_s"]
