"""The benchmark: harness, traffic, yardstick and its tests (see run.py)."""
