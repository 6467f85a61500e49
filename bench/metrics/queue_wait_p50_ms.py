"""Median queue wait in the window, enqueue to the coalesce that takes a
request's first rows (``EngineStats`` queue-wait reservoir, runtime/engine.py:
the most recent 4,096 requests)."""
from bench.readout import quantile


def read(run):
    return quantile(run.stats["queue_wait_ms"], 0.50)
