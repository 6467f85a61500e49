"""Find the knee of a fixed-rate cell: one process, one set-up, a window at
each offered rate.

    python bench/sweep.py --workload vgg16_cifar.infer --seed <n> \
        --seconds 5 --rates 250,500,1000,2000

The knee is the highest rate at which completions keep up with arrivals:
nothing shed or failed, and no more than 2% of the window's requests still
unanswered when it closes (more means the backlog grew).  For each rate this
prints one JSON line: offered and completed requests per second, sheds and
other failures, latency quantiles from the due time, the generator's
lateness, and that unanswered share; the last line names the knee.  A cell's
traffic file then fixes its rate as a number; the sweep is not part of a run.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, requests per second")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import numpy as np

    from bench.harness import System, find_devices, load_cell, log
    from bench.readout import quantile

    cell = load_cell(ROOT, args.workload)
    if cell.traffic["loop"] != "open":
        log(f"{args.workload} is not a fixed-rate cell")
        return 2
    if find_devices(cell, require_tpu=True) is None:
        return 1
    system = System(cell, args.seed)
    knee = None
    try:
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            with tempfile.TemporaryDirectory(prefix="sweep-") as tmp:
                w = system.window(Path(tmp), args.seconds, args.seed + i,
                                  traced=False,
                                  traffic=dict(cell.traffic, rate_per_s=rate))
            r = w.req
            due = r.due < args.seconds
            lat = (r.done - r.due)[due & r.ok]
            shed = sum(o == "rejected:OVERLOADED" for o in r.outcome)
            late = (r.done > r.closed_at) | ~r.ok
            unanswered = float(np.mean(late[due])) if due.any() else 0.0
            keeps_up = shed == 0 and w.attempted == w.ok and unanswered <= 0.02
            if keeps_up and (knee is None or rate > knee):
                knee = rate
            print(json.dumps({
                "rate_per_s": rate,
                "attempted": w.attempted,
                "completed_per_s": float(np.sum(r.ok & (r.done <= args.seconds)))
                / args.seconds,
                "shed": int(shed),
                "failed": w.attempted - w.ok,
                "p50_ms": (quantile(lat, 0.5) or 0.0) * 1e3,
                "p99_ms": (quantile(lat, 0.99) or 0.0) * 1e3,
                "unanswered_at_close": unanswered,
                "keeps_up": keeps_up,
                "lateness_p99_ms": (quantile(w.late, 0.99) or 0.0) * 1e3,
                "flush_device_p50_ms": quantile(w.stats["phase_ms"]["device"], 0.5),
                "rows_per_microbatch": w.stats["rows_in"] / max(1, w.stats["microbatches"]),
            }), flush=True)
    finally:
        system.close()
    print(json.dumps({"knee_per_s": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
