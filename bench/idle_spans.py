"""Put a traced run's idle device time down to the program's own spans.

    python bench/idle_spans.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as ``bench/run.py --trace 1`` does and prints its result line.
Before it, on standard error, one line per host span the program opens
(``repro.runtime.tracing.SPANS``): how many start in the window, their
median duration, and the idle device seconds they cover; then the idle
seconds no program span covers.  A gap that spans of two threads both cover
counts under each, so the lines can add up to more than the idle time.  A
last line gives the window's queue waits (``EngineStats``: enqueue to the
coalesce that takes a request's first rows).
"""
from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, spans, trace as tr
    from bench.readout import quantile
    from repro.runtime.tracing import SPANS

    seen = []
    load = tr.load

    def keep(path):
        seen.append(load(path))
        return seen[-1]

    tr.load = keep
    stats = []
    snapshot = harness.snapshot_stats

    def with_queue_waits(engine_stats):
        stats.append(list(engine_stats._queue_wait_ms))
        return snapshot(engine_stats)

    harness.snapshot_stats = with_queue_waits
    result = harness.run(ROOT, args.workload, args.seed, args.seconds, True,
                         started=STARTED)
    if result is None or not seen:
        return 1
    trace = seen[-1]
    win = tr.window(trace)
    idle = spans.idle_by_span(trace, win, SPANS)
    for name in SPANS:
        ms = spans.durations_ms(trace, win, name)
        med = quantile(ms, 0.50)
        harness.log(f"span {name}: {len(ms)} in the window, median "
                    f"{'-' if med is None else f'{med:.4f}'} ms, idle device "
                    f"{idle[name]:.4f} s")
    total = (win[1] - win[0]) / 1e9 - (tr.busy_s(trace, win) or 0.0)
    harness.log(f"idle under no program span: {idle['no program span']:.4f} "
                f"s of {total:.4f} s idle")
    waits = stats[-1]
    harness.log(f"queue wait: {len(waits)} requests, p50 "
                f"{quantile(waits, 0.50)} ms, p99 {quantile(waits, 0.99)} ms")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
