"""A whole benchmark run rehearsed on the CPU at a tiny geometry.

The tiny cell is added as a later change adds one -- a configuration file, a
traffic file and a metric reader beside the benchmark's own, and entries
appended to ``BENCHMARK.json`` -- and the harness takes it in with no file of
the benchmark edited.  The rehearsal skips only the harness's look for a TPU:
the front door, the load generator in its own process, the window, the
check against the float64 reference and the metric readers all run.  With the
timed path broken underneath, the same run must come out not correct.
"""
import json
import os
import shutil
import subprocess
import sys
import time

import jax.numpy as jnp
import pytest

from bench_tiny import ROOT, tiny_root
from bench import harness


@pytest.fixture(autouse=True, scope="module")
def no_compile_cache(tmp_path_factory):
    """Keep the runs' cache settings out of the other tests in this worker:
    with ``JAX_COMPILATION_CACHE_DIR`` set the program's cache call sets no
    directory, and the harness's thresholds are put back afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    mp = pytest.MonkeyPatch()
    mp.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path_factory.mktemp("cache")))
    yield
    mp.undo()
    for k, v in before.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def _run(root, seed=2 ** 32 + 17, traced=False):
    return harness.run(root, "tiny.closed", seed, 1.5, traced,
                       started=time.perf_counter(), require_tpu=False)


@pytest.fixture(scope="module")
def interpret_run(no_compile_cache, tmp_path_factory):
    root = tiny_root(tmp_path_factory.mktemp("interpret"), "interpret")
    return root, _run(root)


def test_rehearsal_prints_a_well_formed_result(interpret_run):
    _, result = interpret_run
    line = json.loads(json.dumps(result))        # the line is plain JSON
    assert list(line)[-1] == "check"              # the check comes last
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True, line["check"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] >= 1
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    checks = line["check"]
    assert checks["max_abs_err"]["value"] <= checks["max_abs_err"]["limit"]
    assert checks["lost"]["value"] == 0
    assert checks["unpermuted_tenants"]["value"] == 0
    # Every tenant is compared: two requests of 64 images from each.
    assert checks["compared_tenants"]["value"] == 2
    assert checks["compared_images"]["value"] == 2 * 2 * 64


def test_a_cell_is_taken_in_from_files_added_by_name(interpret_run):
    root, result = interpret_run
    # Nothing of the benchmark was edited to add the cell.
    for path in (ROOT / "bench").rglob("*"):
        if path.is_file() and "__pycache__" not in path.parts:
            rel = path.relative_to(ROOT)
            assert (root / rel).read_bytes() == path.read_bytes(), rel
    cell = harness.load_cell(root, "tiny.closed")
    assert cell.config["geometry"]["m"] == 8
    assert cell.traffic["images_per_request"] == 64
    assert [m["name"] for m in cell.end_to_end] == [
        "images_per_s", "hbm_peak_gib", "setup_s"]
    # hbm_peak_gib has nothing to read on a CPU and is left out of the line.
    assert set(result["metrics"]) == {"images_per_s", "setup_s"}


def test_traced_rehearsal_reads_the_cells_own_metric(interpret_run, tmp_path):
    root = tiny_root(tmp_path, "jnp")
    result = _run(root, seed=5, traced=True)
    assert result["correct"] is True, result
    assert result["metrics"]["tiny_ok_requests"]["value"] == result["attempted"], result
    assert result["metrics"]["rows_per_microbatch"]["value"] > 0
    # A split metric read by the reader of its stem, flush_device_ms.py.
    assert result["metrics"]["flush_device_ms.train"]["value"] > 0
    assert result["device"]["window_s"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def _altered(step):
    def faulty(*a, **k):
        return step(*a, **k).at[..., 0].add(1e-3)
    return faulty


def _half_left_out(step):
    def faulty(*a, **k):
        out = step(*a, **k)
        return out.at[:, out.shape[1] // 2:].set(0.0)
    return faulty


def _unpermuted(monkeypatch):
    """The Aug-Conv matrix fused without the provider's channel permutation:
    the step serves the plain channel order."""
    from repro.core import aug_conv

    monkeypatch.setattr(aug_conv, "permute_channel_groups",
                        lambda fused, perm, n: fused)


def _in_step(fault):
    def plant(monkeypatch):
        from repro.runtime import engine

        monkeypatch.setattr(engine, "_delivery_step",
                            fault(engine._delivery_step))
    return plant


@pytest.mark.parametrize(
    "plant", [_in_step(_altered), _in_step(_half_left_out), _unpermuted],
    ids=["answer-altered", "half-of-the-rows-left-out", "output-unpermuted"])
def test_a_broken_timed_path_is_not_correct(tmp_path, monkeypatch, plant):
    plant(monkeypatch)
    result = _run(tiny_root(tmp_path, "jnp"), seed=9)
    assert result["correct"] is False
    assert (result["check"]["max_abs_err"]["value"]
            > result["check"]["max_abs_err"]["limit"])


def test_sound_jnp_run_is_correct_on_another_seed(tmp_path):
    result = _run(tiny_root(tmp_path, "jnp"), seed=2 ** 31 + 3)
    assert result["correct"] is True, result["check"]


def _refuses(cwd, env):
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "vgg16_cifar.infer",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240,
    )
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


@pytest.mark.parametrize("where", ["checkout", "benchmark-alone"])
def test_run_refuses_without_a_tpu(tmp_path, where):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("PYTHONPATH", None)
    cwd = ROOT
    if where == "benchmark-alone":
        cwd = tmp_path / "alone"
        for path in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
            shutil.copytree(ROOT / path, cwd / path,
                            ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copy(ROOT / "BENCHMARK.json", cwd)
    _refuses(cwd, env)


def test_fault_helpers_change_what_they_claim():
    x = jnp.ones((2, 4, 3))
    assert float(_altered(lambda: x)()[0, 0, 0]) == pytest.approx(1.001)
    assert float(_half_left_out(lambda: x)()[:, 2:].sum()) == 0.0
