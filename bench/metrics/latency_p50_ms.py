"""Median latency of the requests due in the window, due time to response."""
from bench.readout import latencies_s, quantile


def read(run):
    q = quantile(latencies_s(run), 0.50)
    return None if q is None else q * 1e3
