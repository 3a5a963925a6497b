"""The chip benchmark: one cell of BENCHMARK.json per run (see run.py)."""
