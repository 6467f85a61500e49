"""The trace reduction (bench/trace.py), the work counts (bench/work.py) and
the metric readers that use them, on synthetic events with known answers and
on a trimmed trace recorded on a TPU v5e."""
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import bench_tiny  # noqa: F401  (puts the checkout on sys.path)
from bench import trace as tr, work
from bench.harness import ROOT, load_reader

RECORDED = Path(__file__).with_name("v5e_trace.json")
PEAK = work.peak_of("TPU v5 lite", ROOT)
VGG = json.loads((ROOT / "bench/configs/vgg16_cifar.json").read_text())["geometry"]


def synthetic() -> dict:
    ms = 1e6
    return {"planes": [
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": [
                ["bench.window", 0.0, 100 * ms],
                ["bench.flush.publish", 18 * ms, 10 * ms],
                ["bench.server.complete", 60 * ms, 30 * ms],
            ]},
            {"name": "flusher", "events": [
                ["bench.flush.coalesce", 10 * ms, 15 * ms],
                ["other span", 0.0, 100 * ms],
            ]},
        ]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit__delivery_step(1)", -5 * ms, 10 * ms],
                ["jit__delivery_step(1)", 30 * ms, 20 * ms],
                ["jit_other", 95 * ms, 10 * ms],
            ]},
            {"name": "XLA Ops", "events": [
                ["prelude", -5 * ms, 10 * ms],          # half before the window
                ["aug_gemm", 30 * ms, 12 * ms],
                ["morph", 32 * ms, 4 * ms],             # overlaps aug_gemm
                ["aug_gemm", 44 * ms, 6 * ms],
                ["tail", 95 * ms, 10 * ms],             # half after it
            ]},
        ]},
    ]}


def test_window_busy_and_modules():
    t = synthetic()
    win = tr.window(t)
    assert win == (0.0, 100e6)
    # [0,5) + [30,42) + [44,50) + [95,100): the overlap counts once.
    assert tr.busy_s(t, win) == pytest.approx(0.028)
    mods = tr.modules(t, win, "_delivery_step")
    assert mods == [(30e6, 20e6)]          # the one starting before is left out
    ops = dict(tr.top_ops(t, win))
    assert ops["aug_gemm"] == pytest.approx(0.018)
    assert ops["prelude"] == pytest.approx(0.005)


def test_idle_gaps_go_to_the_host_span_that_covers_them_most():
    gaps = dict(tr.idle_gaps(synthetic(), (0.0, 100e6)))
    # Gaps: [5,30) coalesce 15 ms vs publish 10 ms; [42,44) nothing;
    # [50,95) complete 30 ms.  "other span" is not the benchmark's.
    assert gaps["bench.flush.coalesce (1 gaps)"] == pytest.approx(0.025)
    assert gaps["bench.server.complete (1 gaps)"] == pytest.approx(0.045)
    assert gaps["no benchmark span (1 gaps)"] == pytest.approx(0.002)


def test_merge_clips_and_joins():
    got = tr.merge([(5, 9), (-3, 2), (8, 12), (20, 30)], 0, 25)
    np.testing.assert_array_equal(got, [[0, 2], [5, 12], [20, 25]])


def test_work_counts_at_the_paper_geometry():
    s = work.shapes(VGG)
    assert (s["f_in"], s["f_out"], s["q"]) == (3072, 65536, 3072)
    flop, nbytes = work.counts(VGG, 1, 1)
    # One tenant's slot: 805 MB Aug-Conv matrix + 37.7 MB core, plus a row.
    assert nbytes == 4 * (3072 * 65536 + 3072 ** 2 + 3072 + 65536)
    assert flop == pytest.approx(421.5e6, rel=1e-3)
    # The morph's and the Aug-Conv product's FLOP; each tenant's secrets once.
    flop, nbytes = work.counts(VGG, 8, 3)
    assert flop == 2 * 8 * 3072 * 3072 + 2 * 8 * 3072 * 65536
    assert nbytes == 4 * (3 * (3072 ** 2 + 3072 * 65536) + 8 * (3072 + 65536))
    # Bytes bound it: 843 MB at 819 GB/s is about 1.03 ms.
    assert work.least_seconds(VGG, 10, 1, PEAK) == pytest.approx(1.03e-3, rel=0.01)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        work.peak_of("cpu", ROOT)


def _record(n_steps, step_s, aug_s, log):
    """A traced run of ``n_steps`` delivery steps, each ``step_s`` long with
    one Aug-Conv call of ``aug_s``, and the work ``log`` the harness kept."""
    events_m, events_o = [], []
    for i in range(n_steps):
        t0 = 1e6 + i * 1e8
        events_m.append(["jit__delivery_step(7)", t0, step_s * 1e9])
        events_o.append(["%_aug_conv_forward_grouped.1 = f32[4,8,65536] custom-call(...)",
                         t0, aug_s * 1e9])
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": events_m},
        {"name": "XLA Ops", "events": events_o}]}]}
    win = (0.0, 1e6 + n_steps * 1e8)
    return SimpleNamespace(geom=VGG, trace={
        "plain": trace, "win": win, "peak": PEAK, "work": log,
        "busy_s": tr.busy_s(trace, win), "window_s": (win[1] - win[0]) / 1e9})


def test_roofline_and_mfu_readers():
    least = work.least_seconds(VGG, 8, 4, PEAK)
    log = [(0.0, 8, 4, 4, 8)] * 10
    rec = _record(10, 2 * least, 1.5 * least, log)
    assert load_reader(ROOT, "delivery_step_roofline")(rec) == pytest.approx(50.0)
    mfu = load_reader(ROOT, "delivery_mfu")(rec)
    assert mfu == pytest.approx(
        100 * work.counts(VGG, 8, 4)[0] / (2 * least * PEAK["bf16_flop_per_s"]))
    # Busy time is the operations' union: here the ten Aug-Conv calls.
    idle = load_reader(ROOT, "device_idle_share")(rec)
    assert idle == pytest.approx(100 * (1 - 15 * least / rec.trace["window_s"]))
    # No traced window: nothing to read, not a number.
    assert load_reader(ROOT, "delivery_step_roofline")(
        SimpleNamespace(geom=VGG, trace=None)) is None
    # A log that disagrees with the trace gives nothing to read either.
    assert load_reader(ROOT, "delivery_step_roofline")(
        _record(30, least, least, log)) is None


def test_recorded_v5e_trace():
    """A trimmed trace of the served step on one TPU v5e (bench/record_trace.py,
    vgg16_cifar.infer): the reduction finds the window, device busy time, the
    delivery step's programs and each kernel's calls, and puts the gaps down
    to the benchmark's spans."""
    t = json.loads(RECORDED.read_text())
    win = tr.window(t)
    assert win is not None and win[1] > win[0]
    assert tr.device_planes(t)
    busy = tr.busy_s(t, win)
    assert 0 < busy < (win[1] - win[0]) / 1e9
    mods = tr.modules(t, win, "_delivery_step")
    assert mods and all(d > 0 for _, d in mods)
    # Programs on one chip do not overlap, and each lies inside busy time.
    assert sum(d for _, d in mods) / 1e9 <= busy * 1.001
    # Both kernels run inside the steps; the Aug-Conv product takes most.
    ops = dict(tr.top_ops(t, win))
    assert max(ops, key=ops.get).startswith("_aug_conv_forward_grouped")
    assert any(n.startswith("_morph_rows_grouped") for n in ops)
    assert sum(ops.values()) < sum(d for _, d in mods) / 1e9
    gaps = tr.idle_gaps(t, win)
    total_idle = sum(s for _, s in gaps)
    assert total_idle == pytest.approx((win[1] - win[0]) / 1e9 - busy)
