"""The decode lane rehearsed on the CPU: a tiny DeepSeek-V2 configuration
(the smoke size) served through ``harness.run(require_tpu=False)`` from new
files only -- its configuration, traffic, and entries appended to a copy of
``BENCHMARK.json`` -- with the check against ``bench/decode_reference.py``.
"""
import json
import shutil
import time

import numpy as np
import pytest

from bench_tiny import ROOT
from bench import decode_work, harness, loadgen
from test_bench_run import no_compile_cache  # noqa: F401  (autouse)

SEED = 2 ** 32 + 41
CELL = "tiny_lm.decode"

TINY_LM = {
    "name": "tiny_lm", "source": "test", "lane": "decode",
    "arch": "deepseek_v2_lite_16b", "smoke": True,
    "hidden_size": 64, "vocab_size": 512, "num_attention_heads": 4,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "intermediate_size": 128, "moe_intermediate_size": 32,
    "n_routed_experts": 8, "num_experts_per_tok": 2, "n_shared_experts": 2,
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "rms_norm_eps": 1e-6,
    "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096, "type": "yarn"},
    "tenants": 2, "capacity": 2, "rows": 4, "max_len": 64,
    "prompt_buckets": [8, 16, 32],
    "front_door_flags": ["--backend", "jnp"],
    "reduced": [],
    # float32 end to end at this size: the error is rounding alone.
    "limits": {"logit_err_std": 1e-3},
}

TINY_DECODE = {
    "loop": "open", "arrivals": "poisson", "rate_per_s": 12,
    "popularity": {"kind": "zipf", "theta": 0.99},
    "prompt_tokens": {"median": 10, "sigma": 0.6, "min": 3, "max": 32},
    "output_tokens": {"median": 5, "sigma": 0.5, "min": 2, "max": 12},
    "connections": 2, "pool_prompts": 256, "check_per_tenant": 2,
    "grace_s": 60,
}

METRICS = ("decode_step_ms", "prefill_ms", "decode_rows_active",
           "expert_gemm_roofline", "decode_step_roofline")


def decode_root(tmp):
    root = tmp / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bench/configs/tiny_lm.json").write_text(json.dumps(TINY_LM))
    (root / "bench/traffic/tiny_decode.json").write_text(json.dumps(TINY_DECODE))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny_lm", "source": "test",
                             "file": "bench/configs/tiny_lm.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny_lm",
                               "traffic": "tiny_decode", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "deepseek_v2_lite.decode" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root


@pytest.fixture(scope="module")
def decode_run(no_compile_cache, tmp_path_factory):  # noqa: F811
    root = decode_root(tmp_path_factory.mktemp("decode"))
    return root, harness.run(root, CELL, SEED, 2.0, False,
                             started=time.perf_counter(), require_tpu=False)


def test_decode_lane_runs_from_new_files_only(decode_run):
    root, result = decode_run
    for path in (ROOT / "bench").rglob("*"):
        if path.is_file() and "__pycache__" not in path.parts:
            rel = path.relative_to(ROOT)
            assert (root / rel).read_bytes() == path.read_bytes(), rel
    line = json.loads(json.dumps(result))
    assert line["correct"] is True, line["check"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"latency_p50_ms", "setup_s"} <= set(line["metrics"])
    check = line["check"]
    assert check["max_logit_err"]["value"] <= check["max_logit_err"]["limit"]
    assert check["compared_tokens"]["value"] > 0
    assert check["compared_tenants"]["value"] == TINY_LM["tenants"]


def test_decode_load_side_draws_the_same_prompts_from_a_seed():
    spec = dict(TINY_DECODE, vocab=512, seconds=1.0, tenants=2, lane="decode")
    a = loadgen.load_lane("decode").Load(spec, SEED)
    b = loadgen.load_lane("decode").Load(spec, SEED)
    for off in (0, 5, 255):
        fa, ba = a.request(off)
        fb, bb = b.request(off)
        assert fa == fb and ba == bb
        assert fa["lane"] == "decode" and 2 <= fa["max_new_tokens"] <= 12
        assert 3 <= fa["shape"][0] <= 32


def test_answer_lengths_cover_the_quantiles_block_by_block():
    """Answer lengths follow the traffic's lognormal in blocks of
    ``STRATA`` requests, one draw from each equal-chance range a block: the
    same seed asks for the same lengths, neighbours are nearly independent
    (long answers come in a row too), and a few hundred requests' median
    sits on the distribution's whatever the seed (independent draws
    scatter it by several percent)."""
    out = {"median": 128, "sigma": 0.7, "min": 16, "max": 512}
    spec = dict(TINY_DECODE, vocab=512, seconds=1.0, tenants=2, lane="decode",
                output_tokens=out)
    load = loadgen.load_lane("decode")
    k = load.STRATA

    def lengths(seed, n=240):
        gen = load.Load(spec, seed)
        return [gen.request(i % 7)[0]["max_new_tokens"] for i in range(n)]

    assert lengths(SEED) == lengths(SEED)
    got = np.array(lengths(SEED, 2000))
    assert got.min() >= 16 and got.max() <= 512
    for block in got[: len(got) // k * k].reshape(-1, k):
        assert (block <= 128).sum() >= k // 2 >= (block < 128).sum()
    # Quartiles of the lognormal: 128 * exp(+-0.6745 * 0.7).
    q1, q3 = np.quantile(got, [0.25, 0.75])
    assert abs(q1 - 80) <= 2 and abs(q3 - 205) <= 3, (q1, q3)
    assert abs(np.corrcoef(got[:-1], got[1:])[0, 1]) < 0.1
    assert np.any((got[:-1] > q3) & (got[1:] > q3))
    medians = [np.median(lengths(SEED + s)) for s in range(6)]
    assert all(abs(m - 128) <= 4 for m in medians), medians
    rng = np.random.default_rng(0)
    loose = [np.median([load.lognormal_len(rng, out) for _ in range(240)])
             for _ in range(6)]
    assert max(abs(m - 128) for m in loose) > 4, loose


def test_expert_work_counts_live_tokens_only():
    """Idle rows and a prompt bucket's padding route to no expert, so the
    routed experts' work counts the live rows and the prompt's own tokens."""
    cfg = json.loads((ROOT / "bench/configs/deepseek_v2_lite.json").read_text())
    k = cfg["num_experts_per_tok"]
    record = {"rows": 30, "hits": [50, 51, 52, 53],
              "prefills": [(300, 512, [40, 41, 42, 43])]}
    calls = decode_work.expert_calls(cfg, record)
    assert calls == [(300 * k, h) for h in (40, 41, 42, 43)] + [
        (30 * k, h) for h in (50, 51, 52, 53)]


def test_prefill_ms_is_the_median_device_time_of_the_prefill_programs():
    """``prefill_ms`` reads the jitted prefills on the device plane inside
    the window, and nothing else."""
    from types import SimpleNamespace

    reader = harness.load_reader(ROOT, "prefill_ms")
    modules = [["jit_counted_prefill", 100e6, 9e6],
               ["jit_counted_prefill", 200e6, 11e6],
               ["jit_counted_prefill", 300e6, 30e6],
               ["jit_counted_decode", 400e6, 18e6],
               ["jit_counted_prefill", 5e9, 1e6]]    # after the window
    plain = {"planes": [{"name": "/device:TPU:0",
                         "lines": [{"name": "XLA Modules", "events": modules}]}]}
    run = SimpleNamespace(trace={"plain": plain, "win": (0.0, 1e9)})
    assert reader(run) == pytest.approx(11.0)
    assert reader(SimpleNamespace(trace=None)) is None
    run.trace["plain"]["planes"][0]["name"] = "/host:CPU"
    assert reader(run) is None


def test_decode_work_counts_published_widths():
    cfg = json.loads((ROOT / "bench/configs/deepseek_v2_lite.json").read_text())
    total, used = decode_work.trunk_params(cfg)
    # 81.0 M parameters in the dense layer, 584.8 M in each MoE layer.
    assert abs(total - (81.0e6 + 4 * 584.8e6)) < 1e6
    assert used < total
    flop, nbytes = decode_work.expert_layer(cfg, pairs=768, hit=64)
    assert nbytes > 64 * 3 * 2048 * 1408 * 2


def test_decode_lane_traced_run_reads_its_spans(tmp_path):
    """A traced run reads the lane's spans and step records; the rooflines
    need a chip's peaks and the prefill's device time a device plane, so
    they stay None on the CPU."""
    root = decode_root(tmp_path)
    result = harness.run(root, CELL, SEED + 1, 2.0, True,
                         started=time.perf_counter(), require_tpu=False)
    assert result["correct"] is True, result["check"]
    got = result["metrics"]
    for name in ("decode_step_ms", "decode_rows_active",
                 "flush_device_ms.infer", "flush_dispatch_ms.infer",
                 "flush_fetch_ms.infer", "queue_wait_p50_ms"):
        assert got[name]["value"] > 0, name
    assert "submit_wait_p99_ms" in got
    assert "expert_gemm_roofline" not in got
    assert "prefill_ms" not in got
