"""Median device phase of a flush (runtime/engine.py ``execute_flush``: the
jitted steps and the copy of their results to the host).  Read for both
``flush_device_ms.infer`` and ``flush_device_ms.train``."""
from bench.readout import quantile


def read(run):
    return quantile(run.stats["phase_ms"]["device"], 0.50)
