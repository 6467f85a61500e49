"""Median publish phase of a flush (runtime/engine.py ``publish_flush``: the
scatter of results into each request's buffer)."""
from bench.readout import quantile


def read(run):
    return quantile(run.stats["phase_ms"]["publish"], 0.50)
