"""Mean rows decoded a step: the live rows of each batched decode step
dispatched in the traced window, as the step's record counts them
(runtime/decode.py ``DecodeWork.record``, handed over by the lane's
``note``)."""
import numpy as np


def read(run):
    if run.trace is None:
        return None
    rows = [s[2]["rows"] for s in run.trace["work"]
            if s[1] == "decode" and "rows" in s[2]]
    return float(np.mean(rows)) if rows else None
