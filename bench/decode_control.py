"""The control of the decode lane's check: the served path with its trunk
weights rounded through float8 (e4m3), the precision below the
configuration's bfloat16, held to the same check as a run.

    python bench/decode_control.py --workload deepseek_v2_lite.decode \
        --seed <n> --seconds 10

It builds the cell's system as a run does, takes the secrets the check reads
(the bfloat16 weights the reference recomputes in float32), rounds every
trunk matrix of the decode lane through ``float8_e4m3fn``, serves one window
and prints one JSON line: each check's value and limit, and whether the
control passed them all.  The check is tight enough when it does not.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import tempfile
import time
from pathlib import Path

STARTED = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    import jax.numpy as jnp

    from bench.harness import System, find_devices, load_cell

    cell = load_cell(ROOT, args.workload)
    if find_devices(cell, require_tpu=True) is None:
        return 1
    system = System(cell, args.seed)
    try:
        secrets = system.secrets()
        lane = system.front.engine.decode_lane
        lane.params = jax.tree.map(
            lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
            if a.ndim >= 2 else a, lane.params)
        with tempfile.TemporaryDirectory(prefix="control-") as tmp:
            w = system.window(Path(tmp), args.seconds, args.seed, traced=False)
    finally:
        system.close()
    del system
    gc.collect()
    checks = cell.lane.checks(cell.config, w.spec, w.sample, secrets, args.seed)
    passed = all(v >= lim if least else v <= lim for _, v, lim, least in checks)
    print(json.dumps({
        "workload": args.workload, "seed": args.seed, "weights": "float8_e4m3fn",
        "passed": passed,
        "check": {n: {"value": v, "limit": lim} for n, v, lim, _ in checks},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
