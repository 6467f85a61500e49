"""Arithmetic shared by the metric readers in ``bench/metrics/``."""
from __future__ import annotations

import numpy as np


def quantile(xs, q: float) -> float | None:
    """Nearest-rank quantile: the smallest value with at least a share
    ``q`` of the values at or below it.  None when there are no values."""
    xs = np.sort(np.asarray(list(xs), np.float64))
    if not xs.size:
        return None
    return float(xs[max(0, int(np.ceil(q * xs.size)) - 1)])


def latencies_s(run) -> np.ndarray:
    """Every request due in the window, from when it was due to when its
    whole response arrived.  A request that failed counts as missing every
    limit: its latency is taken as the time the run waited for it, to the end
    of the grace period after the window."""
    r = run.req
    due = r.due < run.seconds
    horizon = r.closed_at + run.grace_s
    lat = np.where(r.ok, r.done - r.due, horizon - r.due)
    return lat[due]


def per_step(run, seconds: list):
    """Mean least time and mean FLOP over the delivery steps logged in the
    traced window, and the mean of ``seconds`` (each step's device time from
    the trace).  None when the trace holds nothing to read, or when the trace
    and the log disagree on how many steps ran."""
    from bench import work

    t = run.trace
    if t is None or t["peak"] is None or not seconds or not t["work"]:
        return None
    if abs(len(seconds) - len(t["work"])) > max(2, len(t["work"]) // 100):
        return None
    least = [work.least_seconds(run.geom, rows, tenants, t["peak"])
             for _, rows, tenants, _, _ in t["work"]]
    flop = [work.counts(run.geom, rows, tenants)[0]
            for _, rows, tenants, _, _ in t["work"]]
    return float(np.mean(least)), float(np.mean(flop)), float(np.mean(seconds))


def step_seconds(run) -> list:
    """Device time of each jitted ``_delivery_step`` program in the window."""
    from bench import trace as tr

    if run.trace is None:
        return []
    return [d / 1e9 for _, d in tr.modules(run.trace["plain"], run.trace["win"],
                                           "_delivery_step")]
