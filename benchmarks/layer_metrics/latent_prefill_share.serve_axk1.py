"""What latent attention costs prefill at a long reach: own device time
under ``prefill`` / ``attention`` (the bottlenecks, the expansion of every
head's keys and values from the cache, a chunk's for the chunks before it
again, the scores in their blocks, the weighted sums, the output
projection) over all time under ``prefill`` (``scope_times.py``).
Percent."""

from benchmarks import scope_times


def read(record: dict):
    return scope_times.share_of_phase(record, "prefill", ("attention",))
