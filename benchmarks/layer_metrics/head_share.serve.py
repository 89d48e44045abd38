"""What turning the last state into a token costs a cached step: own
device time under ``decode_step`` / ``final_norm``, ``head`` and
``sample`` over all time under ``decode_step`` (``scope_times.py``).
Percent."""

from benchmarks import scope_times


def read(record: dict):
    return scope_times.share_of_phase(record, "decode_step",
                                      ("final_norm", "head", "sample"))
