"""What the mixers that are not attention cost a cached step: own device
time under ``decode_step`` / ``mixer`` (the Mamba-1 layers' norm,
projections, convolution, dt, the step of S and the gate, and the gated
memory units) over all time under ``decode_step`` (``scope_times.py``).
Percent."""

from benchmarks import scope_times


def read(record: dict):
    return scope_times.share_of_phase(record, "decode_step", ("mixer",))
