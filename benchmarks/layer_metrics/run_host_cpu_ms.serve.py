"""Host CPU inside ``run``: the pool thread's ``time.thread_time_ns()``
across the guest's ``execute_task`` (the ledger's ``rcu``), which the span
``faabric:result_push`` carries. The run's wall time less this is time
blocked on the device or off the core. Mean over the traced requests,
milliseconds: a counter's total over its count, and not the median,
because the thread's CPU clock may tick coarsely (10 ms on the v5e
machine's sandboxed kernel: a request reads 0 or 10, my chip run, PR 25),
and only the mean of such readings is near the truth."""

from benchmarks import program_spans


def read(record: dict):
    return program_spans.phase_ms(
        program_spans.of_record(record), "run_host_cpu",
        over=program_spans.mean)
