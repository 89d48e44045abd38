"""The benchmark: harness, yardstick, plain reference, data. See run.py."""
