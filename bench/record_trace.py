"""Record a trimmed trace of one traced run, for the trace reduction's tests.

    python bench/record_trace.py --workload <cell> --seed <n> --seconds <s> \
        --keep-ms 40 --out <file.json>

Runs the cell as ``bench/run.py --trace 1`` does and prints its result line,
then writes the plain trace (``bench/trace.py``'s format) cut to the first
``--keep-ms`` of the window: the window span, the device planes' ``XLA Ops``
and ``XLA Modules`` lines, and the benchmark's own host spans.  It also
prints, on standard error, every plane and line of the full trace with its
event count and a few event names, which shows how the chip names things.
"""
from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def trim(trace: dict, keep_ms: float) -> dict:
    from bench import trace as tr

    lo, hi = tr.window(trace)
    hi = min(hi, lo + keep_ms * 1e6)

    def inside(events):
        return [[n, s, min(d, hi - s)] for n, s, d in events if lo <= s < hi]

    planes = []
    for plane in trace["planes"]:
        if tr.device_planes({"planes": [plane]}):
            lines = [{"name": ln["name"], "events": inside(ln["events"])}
                     for ln in plane["lines"]
                     if ln["name"] in ("XLA Ops", "XLA Modules")]
        else:
            lines = []
            for ln in plane["lines"]:
                keep = [[n, s, d] for n, s, d in ln["events"]
                        if n == tr.WINDOW_SPAN]
                keep = [[n, lo, hi - lo] for n, _, _ in keep]
                keep += [e for e in inside(ln["events"])
                         if e[0].startswith(tr.SPAN_PREFIX)
                         and e[0] != tr.WINDOW_SPAN]
                if keep:
                    lines.append({"name": ln["name"], "events": keep})
        if lines:
            planes.append({"name": plane["name"], "lines": lines})
    return {"planes": planes}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--keep-ms", type=float, default=40.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, trace as tr

    seen = []
    load = tr.load

    def keep(path):
        seen.append(load(path))
        return seen[-1]

    tr.load = keep
    result = harness.run(ROOT, args.workload, args.seed, args.seconds, True,
                         started=STARTED)
    if result is None or not seen:
        return 1
    for plane in seen[-1]["planes"]:
        for ln in plane["lines"]:
            names = sorted({e[0] for e in ln["events"]})
            harness.log(f"plane {plane['name']!r} line {ln['name']!r}: "
                        f"{len(ln['events'])} events, {len(names)} names, "
                        f"e.g. {names[:6]}")
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(trim(seen[-1], args.keep_ms)))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
