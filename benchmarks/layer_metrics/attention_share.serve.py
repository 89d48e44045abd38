"""What attention costs a cached step: own device time under
``decode_step`` / ``attention`` (all of ``attention_sublayer``: norm,
projections, rotary turn, the cache's update, scores, weighted sum, the
output projection, whatever the attention's kind) over all time under
``decode_step`` (``scope_times.py``). Percent."""

from benchmarks import scope_times


def read(record: dict):
    return scope_times.share_of_phase(record, "decode_step", ("attention",))
