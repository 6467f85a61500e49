"""The batched decode step against its roofline: the least time a step needs
(bench/decode_work.py: every trunk weight once, the Aug-head of each slot
its live rows use, each live row's latent cache to its position, or the
FLOP at the bf16 peak) over the device time of the jitted decode programs
(``counted_decode``) in the traced window, as means per step."""
import numpy as np

from bench import decode_work
from bench import trace as tr


def read(run):
    t = run.trace
    if t is None or t["peak"] is None:
        return None
    steps = [s[2] for s in t["work"] if s[1] == "decode" and "rows" in s[2]]
    times = [d / 1e9 for _, d in tr.modules(t["plain"], t["win"],
                                            "counted_decode")]
    if not steps or not times:
        return None
    least = [decode_work.least_seconds(*decode_work.decode_step(run.lm, r),
                                       t["peak"]) for r in steps]
    return 100.0 * float(np.mean(least)) / float(np.mean(times))
