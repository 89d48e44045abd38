"""Runtime, first inbound phase, time waited: the REST body read (``hin``)
→ parsed, admitted (``adm``) → picked up by the planner's tick (``qex``).
From the lifecycle ledger that the program's ``faabric:*`` spans carry into
the trace; median over the traced requests, milliseconds."""

from benchmarks import program_spans


def read(record: dict):
    return program_spans.phase_ms(
        program_spans.of_record(record), "ingress")
