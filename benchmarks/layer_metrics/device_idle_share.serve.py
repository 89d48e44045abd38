"""Share of the traced window in which no operation ran on the chip.
Percent."""


def read(record: dict):
    trace = record.get("trace")
    if not trace or "requests" not in record:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
