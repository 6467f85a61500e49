"""99th percentile of the async front door's submit lock waits in the window
(``EngineStats`` reservoir, runtime/async_engine.py)."""
from bench.readout import quantile


def read(run):
    return quantile(run.stats["submit_wait_ms"], 0.99)
