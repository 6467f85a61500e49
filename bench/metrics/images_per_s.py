"""Images delivered ``ok`` by the end of the window, per second of window."""
import numpy as np


def read(run):
    r = run.req
    done = r.ok & (r.done <= run.seconds)
    return float(np.sum(r.images[done])) / run.seconds
