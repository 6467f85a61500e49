"""Median time the front door spends encoding one result frame: the
program's ``mole.server.encode`` span (launch/server.py ``_complete``:
``wire.encode_result``), in the traced window."""
from bench.spans import median_ms


def read(run):
    return median_ms(run, "mole.server.encode")
