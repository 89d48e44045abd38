"""What prefill costs a request on the device: own device time of the
events under the program's ``prefill`` scope (every chunk of the prompt
through the stack, the head at the last position, the first token's
pick) over the device's busy time of the traced requests
(``scope_times.py``). Percent."""

from benchmarks import scope_times


def read(record: dict):
    return scope_times.share_of_busy(record, "prefill")
