"""A configuration's lane comes from files of its own (bench/lanes/): a lane
is added as new files and appended entries, with no file of the benchmark
edited, and a configuration that names no lane is served by ``rows``."""
import json
import shutil
import time

import numpy as np

from bench_tiny import ROOT, TINY_CONFIG, tiny_root
from bench import harness, loadgen
from test_bench_run import no_compile_cache  # noqa: F401  (autouse)

SEED = 2 ** 32 + 29


def _with_copied_lane(tmp) -> tuple:
    """The tiny checkout plus a lane ``rowcopy``, copied from ``rows`` file
    for file, a configuration that names it and a cell that runs it."""
    root = tiny_root(tmp, "jnp")
    lanes = root / "bench" / "lanes"
    shutil.copy(lanes / "rows.py", lanes / "rowcopy.py")
    shutil.copy(lanes / "rows_load.py", lanes / "rowcopy_load.py")
    tiny = json.loads((root / "bench/configs/tiny.json").read_text())
    (root / "bench/configs/tiny_rowcopy.json").write_text(
        json.dumps(dict(tiny, name="tiny_rowcopy", lane="rowcopy")))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny_rowcopy", "source": "test",
                             "file": "bench/configs/tiny_rowcopy.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny_rowcopy.closed",
                               "config": "tiny_rowcopy",
                               "traffic": "tiny_closed", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny.closed" in m.get("workloads", ()):
            m["workloads"].append("tiny_rowcopy.closed")
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root


def _run(root, cell):
    return harness.run(root, cell, SEED, 1.5, False,
                       started=time.perf_counter(), require_tpu=False)


def test_a_lane_is_added_with_new_files_only(tmp_path):
    root = _with_copied_lane(tmp_path)
    for path in (ROOT / "bench").rglob("*"):
        if path.is_file() and "__pycache__" not in path.parts:
            rel = path.relative_to(ROOT)
            assert (root / rel).read_bytes() == path.read_bytes(), rel
    cell = harness.load_cell(root, "tiny_rowcopy.closed")
    assert cell.lane.__file__ == str(root / "bench/lanes/rowcopy.py")
    spec = harness.loadgen_spec(cell, 1.0)
    assert spec["lane"] == "rowcopy"
    # The checkout's load generator takes the spec's lane from beside itself.
    copied = harness._load(root / "bench/loadgen.py", "bench_copied_loadgen")
    gen = copied.LoadGen(spec, SEED, "127.0.0.1", 0)
    assert type(gen.lane).__module__ == "bench_lane_rowcopy_load"

    rows, copy = _run(root, "tiny.closed"), _run(root, "tiny_rowcopy.closed")
    line = json.loads(json.dumps(copy))
    assert list(line)[-1] == "check"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True, line["check"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == set(rows["metrics"])
    # The same checks with the same limits; every count equal.  The largest
    # error is of whichever requests the seeded reservoir kept from a window
    # whose length in requests varies, so it is held to its limit.
    assert list(copy["check"]) == list(rows["check"])
    for name, got in copy["check"].items():
        assert got["limit"] == rows["check"][name]["limit"], name
        if name != "max_abs_err":
            assert got["value"] == rows["check"][name]["value"], name
    for res in (rows, copy):
        assert res["check"]["max_abs_err"]["value"] <= res["check"][
            "max_abs_err"]["limit"]


def test_a_configuration_without_a_lane_is_rows():
    assert "lane" not in TINY_CONFIG
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = harness.load_cell(ROOT, w["name"])
        assert "lane" not in cell.config
        assert harness.lane_name(cell.config) == "rows"
        assert cell.lane.__file__ == str(ROOT / "bench/lanes/rows.py")
        assert harness.loadgen_spec(cell, 1.0)["lane"] == "rows"
    assert loadgen.load_lane("rows").__file__ == str(
        ROOT / "bench/lanes/rows_load.py")


class _Tokens:
    """A load side that adds a column, as a generation lane adds the tokens
    each response carried."""
    columns = ("tokens",)
    images = 1
    choices = 4

    def response(self, header, payload):
        return (int(np.prod(header["shape"])),)

    def sample(self, kept):
        return {"offsets": np.array([o for o, _ in kept], np.int64)}


def test_a_lane_adds_columns_to_what_the_load_generator_records(tmp_path):
    gen = loadgen.LoadGen({"seconds": 1.0, "lane": "rows", "channels": 1,
                           "image_size": 2, "pool_images": 4,
                           "images_per_request": 1}, 3, "127.0.0.1", 0)
    gen.lane = _Tokens()
    gen.closed_at = 1.0
    for i, outcome in enumerate([loadgen.OK, loadgen.TIMEOUT]):
        r = loadgen._Request(i, 0, i, 0.1 * i)
        r.sent = r.due
        if outcome == loadgen.OK:
            r.extra = gen.lane.response({"shape": [3, 5]}, b"")
            gen._res[0] = (1, [(r, None)])
        r.resolve(outcome, 0.5 if outcome == loadgen.OK else None)
        gen.reqs[str(i)] = r
    gen.write(tmp_path)
    req = harness._requests(tmp_path)
    assert req.extra == {"tokens": [15, None]}
    assert req.ok.tolist() == [True, False]
    with np.load(tmp_path / "sample.npz") as z:
        assert sorted(z.files) == ["idx", "offsets", "tenant"]
        assert z["offsets"].tolist() == [0]
