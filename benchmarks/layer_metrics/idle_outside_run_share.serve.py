"""The idle time the runtime owns: share of the traced window in which no
XLA module runs on the chip and no ``faabric:run`` span is open.
``device_idle_share.serve`` less this is the guest's and the program's
own. Percent."""

from benchmarks import program_spans


def read(record: dict):
    return program_spans.idle_outside_run_share(
        program_spans.of_record(record))
