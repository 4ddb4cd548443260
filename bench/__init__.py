"""The chip benchmark: see `BENCHMARK.json` at the root and `bench/run.py`."""
