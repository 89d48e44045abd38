"""What the dense feed-forwards cost a cached step: own device time under
``decode_step`` / ``feed_forward`` (the norm before it, the products or
the streaming kernel, the residual; an expert layer's ``router`` and
``experts`` are not in it) over all time under ``decode_step``
(``scope_times.py``). Percent."""

from benchmarks import scope_times


def read(record: dict):
    return scope_times.share_of_phase(record, "decode_step",
                                      ("feed_forward",))
