"""Chip benchmark of the serving engine: one cell per run, driven by
``BENCHMARK.json`` and the data files beside this package."""
