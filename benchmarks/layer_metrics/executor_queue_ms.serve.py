"""Runtime, third inbound phase, time waited: the dispatch RPC about to be
written (``dsp``) → the wire, the worker's function server, the hand-off
to a pool thread (``eqx``). From the lifecycle ledger in the program's
``faabric:*`` spans; median over the traced requests, milliseconds."""

from benchmarks import program_spans


def read(record: dict):
    return program_spans.phase_ms(
        program_spans.of_record(record), "executor_queue")
