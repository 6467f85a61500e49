"""Benchmark harness: one module per paper table/figure (see run.py)."""
