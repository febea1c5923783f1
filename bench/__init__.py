"""Benchmark of the serving system on the chip: see BENCHMARK.json and
bench/run.py."""
