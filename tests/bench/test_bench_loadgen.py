"""The load generator (bench/loadgen.py), the rows lane's load side
(bench/lanes/rows_load.py), and the latency arithmetic the metric readers
apply to what it records."""
import json
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

import bench_tiny
from bench import loadgen
from bench.harness import ROOT, load_reader
from bench.lanes import rows_load
from bench.readout import latencies_s, quantile

SPEC = dict(
    json.loads((ROOT / "bench/traffic/infer.json").read_text()),
    seconds=4.0, tenants=4, channels=3, image_size=32,
)
CHOICES = rows_load.Load(SPEC, 0).choices


def test_loadgen_imports_no_jax():
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, 'bench'); import loadgen; "
         "loadgen.load_lane('rows'); "
         "print(sorted(m for m in sys.modules if m.split('.')[0] == 'jax'))"],
        cwd=bench_tiny.ROOT, capture_output=True, text=True, timeout=60,
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "[]"


def test_open_schedule_is_drawn_from_the_seed():
    a = loadgen.open_schedule(SPEC, 2 ** 33 + 5, CHOICES)
    b = loadgen.open_schedule(SPEC, 2 ** 33 + 5, CHOICES)
    c = loadgen.open_schedule(SPEC, 2 ** 33 + 6, CHOICES)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
    assert a["due"].size != c["due"].size or not np.array_equal(a["due"], c["due"])
    assert np.all(np.diff(a["due"]) >= 0) and a["due"].max() < SPEC["seconds"]
    assert 0 <= a["offset"].min() and a["offset"].max() < CHOICES
    # Poisson at the file's rate: the count is within a few deviations.
    mean = SPEC["rate_per_s"] * SPEC["seconds"]
    assert abs(a["due"].size - mean) < 5 * np.sqrt(mean)


def test_zipf_popularity():
    w = loadgen.zipf_weights(4, 0.99)
    assert w.sum() == pytest.approx(1.0)
    assert w[0] / w[1] == pytest.approx(2 ** 0.99)
    sch = loadgen.open_schedule(dict(SPEC, seconds=50.0), 3, CHOICES)
    share = np.bincount(sch["tenant"], minlength=4) / sch["tenant"].size
    np.testing.assert_allclose(share, w, atol=0.02)


def test_image_pool_is_drawn_from_the_seed():
    a = rows_load.image_pool(SPEC, 11)
    assert a.shape == (SPEC["pool_images"], 3, 32, 32) and a.dtype == np.float32
    np.testing.assert_array_equal(a, rows_load.image_pool(SPEC, 11))


def test_request_frame_is_the_wire_format():
    from repro.runtime import wire

    images = np.arange(2 * 3 * 4 * 4, dtype=np.float32).reshape(2, 3, 4, 4)
    frame = loadgen.encode_request("7", "tenant-1",
                                   *rows_load.request_fields(images))
    kind, header, payload = wire.decode_frame(frame)
    rid, age, req = wire.decode_request(header, payload)
    assert kind == wire.KIND_REQ and rid == "7" and age == 0.0
    assert req.tenant_id == "tenant-1" and req.lane == "rows"
    np.testing.assert_array_equal(req.payload, images)
    assert wire.decode_frame(loadgen.encode_bye())[0] == wire.KIND_BYE


def _run(due, done, ok, seconds=1.0, closed_at=1.0, grace=60.0):
    return SimpleNamespace(
        seconds=seconds, grace_s=grace,
        req=SimpleNamespace(due=np.asarray(due, float), done=np.asarray(done, float),
                            ok=np.asarray(ok, bool), closed_at=closed_at,
                            images=np.ones(len(due), int)),
    )


def test_latency_runs_from_the_due_time_and_failures_count_as_missing():
    run = _run(due=[0.0, 0.1, 0.2, 0.3, 1.5], done=[0.01, 0.13, 0.3, np.nan, 1.6],
               ok=[True, True, True, False, True])
    lat = latencies_s(run)
    # The request due after the window is not counted; the failed one waits
    # to the end of the grace period.
    np.testing.assert_allclose(lat, [0.01, 0.03, 0.1, 60.7])
    assert load_reader(ROOT, "latency_p50_ms")(run) == pytest.approx(30.0)
    assert quantile(lat, 0.99) == pytest.approx(60.7)
    # Images are counted when delivered ok inside the window.
    assert load_reader(ROOT, "images_per_s")(run) == pytest.approx(3.0)


def test_nearest_rank_quantile():
    assert quantile(range(1, 101), 0.99) == 99.0
    assert quantile(range(1, 101), 0.5) == 50.0
    assert quantile([], 0.5) is None
