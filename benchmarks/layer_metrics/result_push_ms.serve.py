"""Runtime, the worker's half of the way back: the guest has returned →
the result's push RPC is written, the span ``faabric:result_push``. Median
over the traced requests, milliseconds."""

from benchmarks import program_spans


def read(record: dict):
    return program_spans.phase_ms(
        program_spans.of_record(record), "result_push")
