"""Reduction of a profiler trace to the benchmark's device numbers.

A trace is kept as plain data: ``{"planes": [{"name", "lines": [{"name",
"events": [[name, start_ns, duration_ns], ...]}]}]}``.  :func:`load` builds
it from the ``.xplane.pb`` the JAX profiler writes; the functions below read
only that structure, so a small recorded trace tests them without a chip.

* Device planes are ``/device:TPU:<n>``.  An operation is an event on a
  device plane's ``XLA Ops`` line; a jitted program is an event on its
  ``XLA Modules`` line.
* The window is the host span ``bench.window`` the harness opens while the
  load runs.  Busy time is the union of the operations' intervals inside it,
  per chip, averaged over the chips.
* Idle gaps are the window's stretches that no operation covers.  Each gap is
  put down to the host span of the benchmark's own (``bench.*``, other than
  the window) that overlaps it most: what the host was doing while the
  device waited.

Nothing here touches a device; ``load`` imports JAX only to parse the file.
"""
from __future__ import annotations

import collections
import re

import numpy as np

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
_DEVICE = re.compile(r"^/device:TPU:\d+$")


def load(path) -> dict:
    """The plain trace of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(str(path)).planes:
        lines = []
        for line in plane.lines:
            lines.append({
                "name": line.name,
                "events": [
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events
                ],
            })
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(trace: dict) -> list[dict]:
    return [p for p in trace["planes"] if _DEVICE.match(p["name"])]


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def window(trace: dict) -> tuple[float, float] | None:
    """``(start_ns, end_ns)`` of the ``bench.window`` host span."""
    for plane in trace["planes"]:
        if _DEVICE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name == WINDOW_SPAN:
                    return start, start + dur
    return None


def merge(intervals, lo: float, hi: float) -> np.ndarray:
    """Disjoint sorted ``(k, 2)`` union of ``[start, end)`` intervals, clipped
    to ``[lo, hi)``."""
    iv = np.asarray(intervals, np.float64).reshape(-1, 2)
    iv = np.clip(iv, lo, hi)
    iv = iv[iv[:, 1] > iv[:, 0]]
    if not len(iv):
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def _covered(merged: np.ndarray, t: np.ndarray) -> np.ndarray:
    """Length of ``merged`` lying before each time in ``t``."""
    if not len(merged):
        return np.zeros_like(t, dtype=np.float64)
    lengths = merged[:, 1] - merged[:, 0]
    before = np.concatenate([[0.0], np.cumsum(lengths)])
    i = np.searchsorted(merged[:, 0], t, side="right") - 1
    inside = np.where(
        i >= 0,
        np.minimum(t, merged[np.maximum(i, 0), 1]) - merged[np.maximum(i, 0), 0],
        0.0,
    )
    return np.where(i >= 0, before[np.maximum(i, 0)] + inside, 0.0)


def _ops(plane: dict) -> list:
    return _line(plane, "XLA Ops") or _line(plane, "XLA Modules")


def op_name(name: str) -> str:
    """An operation's HLO instruction name: the TPU trace names each
    operation by its whole instruction text, ``%name = type op(...)``."""
    return name.split(" = ", 1)[0].lstrip("%")


def busy(trace: dict, win: tuple[float, float]) -> dict:
    """Per-chip busy union inside the window and its gaps (nanoseconds)."""
    lo, hi = win
    out = {}
    for plane in device_planes(trace):
        merged = merge([(s, s + d) for _, s, d in _ops(plane)], lo, hi)
        edges = np.concatenate([[lo], merged.reshape(-1), [hi]]).reshape(-1, 2)
        gaps = edges[edges[:, 1] > edges[:, 0]]
        out[plane["name"]] = {"merged": merged, "gaps": gaps}
    return out


def busy_s(trace: dict, win: tuple[float, float]) -> float | None:
    """Seconds in which an operation ran, averaged over the chips."""
    per = busy(trace, win)
    if not per:
        return None
    total = [float((b["merged"][:, 1] - b["merged"][:, 0]).sum()) for b in per.values()]
    return float(np.mean(total)) / 1e9


def modules(trace: dict, win: tuple[float, float], pattern: str) -> list:
    """``(start_ns, duration_ns)`` of each jitted-program event whose name
    holds ``pattern`` and that starts inside the window, on every chip."""
    lo, hi = win
    return sorted(
        (s, d)
        for plane in device_planes(trace)
        for name, s, d in _line(plane, "XLA Modules")
        if pattern in name and lo <= s < hi
    )


def top_ops(trace: dict, win: tuple[float, float], k: int = 10) -> list:
    """The ``k`` operations with the most device seconds in the window,
    summed by instruction name over the chips."""
    lo, hi = win
    acc: collections.Counter = collections.Counter()
    for plane in device_planes(trace):
        for name, s, d in _ops(plane):
            e = min(s + d, hi) - max(s, lo)
            if e > 0:
                acc[op_name(name)] += e / 1e9
    return [[n, v] for n, v in acc.most_common(k)]


def host_spans(trace: dict) -> dict[str, list]:
    """Host intervals of the benchmark's own spans, by name."""
    out: dict[str, list] = collections.defaultdict(list)
    for plane in trace["planes"]:
        if _DEVICE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, s, d in line["events"]:
                if name.startswith(SPAN_PREFIX) and name != WINDOW_SPAN:
                    out[name].append((s, s + d))
    return out


def idle_gaps(trace: dict, win: tuple[float, float], k: int = 10) -> list:
    """Idle device seconds in the window, summed by what the host was doing.

    Each gap goes to the benchmark span that overlaps it most, or to ``"no
    benchmark span"``; the ``k`` largest totals are returned, with the gap
    count in the name.
    """
    lo, hi = win
    spans = {n: merge(iv, lo, hi) for n, iv in host_spans(trace).items()}
    seconds: collections.Counter = collections.Counter()
    count: collections.Counter = collections.Counter()
    for b in busy(trace, win).values():
        gaps = b["gaps"]
        if not len(gaps):
            continue
        names = ["no benchmark span"] + list(spans)
        overlap = np.zeros((len(names), len(gaps)))
        for i, n in enumerate(names[1:], start=1):
            overlap[i] = _covered(spans[n], gaps[:, 1]) - _covered(spans[n], gaps[:, 0])
        who = np.where(overlap.max(axis=0) > 0, overlap.argmax(axis=0), 0)
        for g, w in zip(gaps, who):
            seconds[names[w]] += (g[1] - g[0]) / 1e9
            count[names[w]] += 1
    n_chips = max(1, len(device_planes(trace)))
    return [
        [f"{n} ({count[n]} gaps)", v / n_chips]
        for n, v in seconds.most_common(k)
    ]
