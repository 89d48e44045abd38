"""Share of the traced window in which no operation ran, mean over the
gang's chips. Percent."""


def read(record: dict):
    trace = record.get("trace")
    if not trace or "steps" not in record:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
