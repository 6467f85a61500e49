"""Median time a flush spends copying its results to the host: the
program's ``mole.flush.fetch`` spans (runtime/engine.py ``execute_flush``:
``np.asarray`` of each work item's ready output) summed inside each
``mole.flush.device`` span of the traced window.  Read for both
``flush_fetch_ms.infer`` and ``flush_fetch_ms.train``."""
from bench.spans import median_ms


def read(run):
    return median_ms(run, "mole.flush.device", inner="mole.flush.fetch")
