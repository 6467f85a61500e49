"""The control of the check: the reference in the precision below the one
the configuration states, put in the program's place.

    python bench/control.py --workload <cell> --seeds 1,2,3

For each seed it draws the cell's kernels and a sample as large as a run
compares (``check_per_tenant`` requests of the traffic's size from every
tenant) and prints the largest ``|control - reference|`` beside the
configuration's limit: float32 at ``highest`` has three bfloat16 passes
(``high``) below it.  A single bfloat16 pass is printed too, for scale.  The
control is not correct when it reads above the limit.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def control_sample(cell, seed: int) -> dict:
    """Tenants and images of a sample the size of a run's."""
    from bench import loadgen
    from bench.harness import loadgen_spec

    spec = loadgen_spec(cell, 10.0)
    pool = loadgen.image_pool(spec, seed)
    k, per = int(spec["check_per_tenant"]), int(spec["images_per_request"])
    tenants = np.repeat(np.arange(spec["tenants"]), k)
    rng = np.random.default_rng([seed, 4])
    offsets = rng.integers(0, spec["pool_images"] - per + 1, size=tenants.size)
    return {"tenant": tenants,
            "images": np.stack([pool[o : o + per] for o in offsets])}


def readings(cell, seed: int) -> dict:
    from bench import reference

    geom = cell.config["geometry"]
    kernels = reference.developer_kernels(geom, cell.config["tenants"], seed)
    sample = control_sample(cell, seed)

    def one_pass(x, k, g):
        return reference.conv(reference.bf16(x), reference.bf16(k), g)

    return {
        "seed": seed,
        "high": reference.compare(sample, geom, kernels, {},
                                  produce=reference.conv_high)["max_abs_err"],
        "bf16": reference.compare(sample, geom, kernels, {},
                                  produce=one_pass)["max_abs_err"],
        "limit": cell.config["limits"]["max_abs_err"],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench.harness import load_cell

    cell = load_cell(ROOT, args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(dict(readings(cell, seed), workload=args.workload)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
