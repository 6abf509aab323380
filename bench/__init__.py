"""Chip benchmark of the adaptive FMM: see ``bench/harness.py`` and
``BENCHMARK.json`` at the root of the repository."""
