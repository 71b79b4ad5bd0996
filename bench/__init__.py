"""The on-chip benchmark: see ``bench/run.py`` and ``BENCHMARK.json``."""
