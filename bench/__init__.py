"""The chip benchmark: harness, yardstick and cells (see run.py)."""
