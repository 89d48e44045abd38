"""Runtime, second inbound phase, time busy: picked up by the tick
(``qex``) → scheduling decision (``sch``) → journal (``jnl``) → the
dispatch RPC about to be written (``dsp``). From the lifecycle ledger in
the program's ``faabric:*`` spans; median over the traced requests,
milliseconds."""

from benchmarks import program_spans


def read(record: dict):
    return program_spans.phase_ms(
        program_spans.of_record(record), "planner")
