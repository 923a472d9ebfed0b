"""Chip benchmark of the sLSM store (see `bench_tpu/run.py`)."""
