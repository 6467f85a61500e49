"""The program's own host spans in a traced window: their durations, and the
idle device time under them.

The served path opens its spans itself (``repro.runtime.tracing``: names
``mole.*``), on the profiler's clock, so they line up with the device planes.
The functions here read the plain trace of ``bench/trace.py`` and never change
it.  A span belongs to the window when it starts inside it.  In a program
that opens no such span each function finds nothing: an empty list, or an
idle time of 0.
"""
from __future__ import annotations

import numpy as np

from bench import trace as tr


def _host_lines(trace: dict):
    """Every line (one per thread) of the trace's host planes."""
    for plane in trace["planes"]:
        if not tr.device_planes({"planes": [plane]}):
            yield from plane["lines"]


def _starts_inside(events, name: str, win) -> np.ndarray:
    """``(k, 2)`` start and end (ns) of the line's spans ``name`` that start
    inside the window, in order of start."""
    lo, hi = win
    iv = np.array([(s, s + d) for n, s, d in events
                   if n == name and lo <= s < hi], np.float64).reshape(-1, 2)
    return iv[np.argsort(iv[:, 0], kind="stable")]


def durations_ms(trace: dict, win, name: str) -> list[float]:
    """Duration of each span ``name`` that starts inside the window, in ms."""
    out: list[float] = []
    for line in _host_lines(trace):
        iv = _starts_inside(line["events"], name, win)
        out.extend(((iv[:, 1] - iv[:, 0]) / 1e6).tolist())
    return out


def summed_within_ms(trace: dict, win, outer: str, inner: str) -> list[float]:
    """For each span ``outer`` that starts inside the window, the summed
    duration of the spans ``inner`` that start inside it on the same thread,
    in ms (0 where it holds none)."""
    out: list[float] = []
    for line in _host_lines(trace):
        outs = _starts_inside(line["events"], outer, win)
        if not len(outs):
            continue
        ins = _starts_inside(line["events"], inner, (outs[0, 0], outs[-1, 1]))
        sums = np.zeros(len(outs))
        i = np.searchsorted(outs[:, 0], ins[:, 0], side="right") - 1
        held = (i >= 0) & (ins[:, 0] < outs[np.maximum(i, 0), 1])
        np.add.at(sums, i[held], ins[held, 1] - ins[held, 0])
        out.extend((sums / 1e6).tolist())
    return out


def idle_under_s(trace: dict, win, names) -> float:
    """Idle device seconds in the window that the spans ``names`` cover, on
    any thread, averaged over the chips."""
    lo, hi = win
    wanted = set(names)
    spans = tr.merge([(s, s + d) for line in _host_lines(trace)
                      for n, s, d in line["events"] if n in wanted], lo, hi)
    per_chip = [
        float(np.sum(tr._covered(spans, b["gaps"][:, 1])
                     - tr._covered(spans, b["gaps"][:, 0])))
        for b in tr.busy(trace, win).values() if len(b["gaps"])
    ]
    return float(np.sum(per_chip)) / max(1, len(tr.device_planes(trace))) / 1e9


def idle_by_span(trace: dict, win, names) -> dict[str, float]:
    """Idle device seconds under each span of ``names`` (a gap two threads'
    spans both cover counts under each), and under none of them."""
    idle = (win[1] - win[0]) / 1e9 - (tr.busy_s(trace, win) or 0.0)
    out = {n: idle_under_s(trace, win, [n]) for n in names}
    out["no program span"] = idle - idle_under_s(trace, win, names)
    return out


def median_ms(run, name: str, inner: str | None = None) -> float | None:
    """A reader's median over the traced window: of the spans ``name``, or,
    given ``inner``, of the time the spans ``inner`` take inside each span
    ``name``.  None when the run was not traced or holds no such span."""
    from bench.readout import quantile

    if run.trace is None:
        return None
    plain, win = run.trace["plain"], run.trace["win"]
    if inner is None:
        return quantile(durations_ms(plain, win, name), 0.50)
    return quantile(summed_within_ms(plain, win, name, inner), 0.50)
