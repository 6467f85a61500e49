"""A tiny cell for the benchmark's CPU tests, added the way a later change
adds one: new files beside the benchmark's own, and entries appended to a copy
of ``BENCHMARK.json``.  No file of the benchmark is edited."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TINY_CONFIG = {
    "name": "tiny",
    "source": "test geometry",
    # beta 8: a drawn permutation is the identity once in 40,320 tenants.
    "geometry": {"alpha": 2, "m": 8, "p": 3, "stride": 1, "pad": 1, "beta": 8,
                 "kappa": 1},
    "precision": "float32",
    "tenants": 2,
    "capacity": 2,
    "front_door_flags": ["--warm-batch", "64", "--backend", "interpret"],
    "reduced": [],
}

TINY_TRAFFIC = {
    "loop": "closed",
    "jobs": "one_per_tenant",
    "depth": 2,
    "images_per_request": 64,
    "popularity": {"kind": "one_job_per_tenant"},
    "pool_images": 256,
    "check_per_tenant": 2,
    "grace_s": 30,
}

# The tiny cell reports every end-to-end metric it can (a closed loop has no
# due-time latencies of interest) and one metric of its own.
TINY_METRIC = '''"""Requests the tiny cell delivered ok in its window."""


def read(run):
    return float(run.req.ok.sum())
'''


def tiny_root(tmp: Path, backend: str = "interpret") -> Path:
    """A checkout-shaped directory holding the benchmark plus a ``tiny.closed``
    cell; the limits are the VGG-16 configuration's own."""
    root = tmp / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    vgg = json.loads((ROOT / "bench/configs/vgg16_cifar.json").read_text())
    cfg = dict(TINY_CONFIG, limits=vgg["limits"])
    cfg["front_door_flags"] = ["--warm-batch", "64", "--backend", backend]
    (root / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/tiny_closed.json").write_text(json.dumps(TINY_TRAFFIC))
    (root / "bench/metrics/tiny_ok_requests.py").write_text(TINY_METRIC)
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "bench/configs/tiny.json", "reduced": [],
                             "why": "test"})
    bench["workloads"].append({"name": "tiny.closed", "config": "tiny",
                               "traffic": "tiny_closed", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "images_per_s":
            m["workloads"].append("tiny.closed")
    bench["per_layer"].append({
        "name": "tiny_ok_requests", "unit": "requests", "better": "higher",
        "source": "host_clock", "layer": "test", "moves": "images_per_s",
        "workloads": ["tiny.closed"],
    })
    for m in bench["per_layer"]:
        if m["name"] in ("rows_per_microbatch", "flush_publish_ms",
                         "flush_device_ms.train"):
            m["workloads"].append("tiny.closed")
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root
