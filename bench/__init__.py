"""The serving benchmark: one command, `python3 bench/run.py`, driven by
BENCHMARK.json at the root of the repository (see bench/spec.py)."""
