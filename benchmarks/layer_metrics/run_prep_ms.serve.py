"""Runtime, last inbound phase: a pool thread has the task (``eqx``) →
the guest is entered (``rns``), the span ``faabric:run_prep``. Median over
the traced requests, milliseconds."""

from benchmarks import program_spans


def read(record: dict):
    return program_spans.phase_ms(
        program_spans.of_record(record), "run_prep")
