"""What the mixers that are not attention cost prefill: own device time
under ``prefill`` / ``mixer`` (the Mamba-1 layers, of which the scan
along positions is most, and the gated memory units at the last position)
over all time under ``prefill`` (``scope_times.py``). Percent."""

from benchmarks import scope_times


def read(record: dict):
    return scope_times.share_of_phase(record, "prefill", ("mixer",))
