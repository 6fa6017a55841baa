"""perfbench: the t3fs chip benchmark (see run.py and BENCHMARK.json)."""
