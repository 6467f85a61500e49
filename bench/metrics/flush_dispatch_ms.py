"""Median host time a flush spends starting its jitted steps: the program's
``mole.flush.dispatch`` span (runtime/engine.py ``execute_flush``: the
uploads of the rows and the calls into the steps, every work item), one a
flush, in the traced window."""
from bench.spans import median_ms


def read(run):
    return median_ms(run, "mole.flush.dispatch")
