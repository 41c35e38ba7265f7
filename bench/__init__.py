"""Chip benchmark of the COPR log store (``BENCHMARK.json``)."""
