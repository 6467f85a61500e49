"""Median device time of one admission prefill: the jitted prefill programs
(``counted_prefill``, runtime/decode.py ``run_step``: a joiner's bucketed
prompt through the trunk into a fresh cache, to its first token) in the
traced window.  None without a device trace."""
import numpy as np

from bench import trace as tr


def read(run):
    t = run.trace
    if t is None:
        return None
    times = [d / 1e6 for _, d in tr.modules(t["plain"], t["win"],
                                            "counted_prefill")]
    return float(np.median(times)) if times else None
