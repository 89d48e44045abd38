"""Process start → start of the measured window: spawning planner and
worker, reaching the chip, weights from the seed, loading or compiling
every program the window uses, and one warm run of each."""


def read(record: dict):
    return record["setup_s"]
