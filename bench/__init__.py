"""The ODYS slave benchmark: see ``bench/run.py`` and ``BENCHMARK.json``."""
