"""Benchmark harness: one cell of BENCHMARK.json, run once (see run.py)."""
