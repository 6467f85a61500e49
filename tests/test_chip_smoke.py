"""``chip_smoke.py`` on the CPU, and the compile-cache setup it calls.

The served phase runs exactly as on the chip (in-process server, two client
fleets, exactly-once and the float32 convolution reference) at a tiny
geometry (alpha=2, m=8, beta=2), with the kernels interpreted; so does the
precision probe.  On a machine without a TPU, and in a directory that
holds nothing of the repository but the script, it must exit non-zero
without printing a result.
"""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch.cache import enable_compile_cache

ROOT = Path(__file__).resolve().parents[1]


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_served_phase_interpret_matches_reference():
    smoke = _load_smoke()
    tiny = [
        "--channels", "2", "--out-channels", "2", "--image-size", "8",
        "--kappa", "1", "--tenants", "2", "--capacity", "2",
        "--warm-batch", "2", "--backend", "interpret",
    ]
    r = smoke.serve_and_check(tiny, requests=6, clients=2, batch=2,
                              trace="uniform:200", seed=3)
    assert r["backend"] == "interpret"
    assert r["images_checked"] == 2 * 6 * 2      # both fleets, every image
    assert r["max_err"] <= smoke.TOL
    assert r["custom_calls"]                      # the step was lowered


def test_precision_probe_interpret():
    """Interpreted on the CPU both products are fp32; the chip is where the
    default-precision control differs."""
    smoke = _load_smoke()
    p = smoke.precision_probe(5, interpret=True)
    assert p["highest"] <= 1e-4 and p["default"] <= 1e-4
    assert p["max_want"] > 1.0


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_refuses_without_tpu(tmp_path, where):
    script = ROOT / "chip_smoke.py"
    if where == "alone":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, str(script)], cwd=script.parent, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


@pytest.mark.parametrize("env_dir", [True, False], ids=["env-set", "env-unset"])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    before = jax.config.jax_compilation_cache_dir
    try:
        if env_dir:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert enable_compile_cache() == str(tmp_path)
            # JAX reads the variable itself; the code sets no directory.
            assert jax.config.jax_compilation_cache_dir == before
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            path = enable_compile_cache()
            assert path == str(ROOT / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
            assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()
