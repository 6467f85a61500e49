"""The program's own spans in a traced window (bench/spans.py) and the metric
readers built on them: on a synthetic trace with known answers, and in a
traced rehearsal of the tiny cell on the CPU."""
import json
import time
from types import SimpleNamespace

import pytest

from bench_tiny import tiny_root
from bench import harness, spans, trace as tr
from bench.harness import ROOT, load_reader
from test_bench_run import no_compile_cache  # noqa: F401  (autouse)

MS = 1e6
WIN = (0.0, 100 * MS)


def synthetic() -> dict:
    return {"planes": [
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": [["bench.window", 0.0, 100 * MS]]},
            {"name": "flusher", "events": [
                ["mole.flush.device", -10 * MS, 8 * MS],    # before the window
                ["mole.flush.fetch", -4 * MS, 2 * MS],
                ["mole.flush.device", 10 * MS, 20 * MS],
                ["mole.flush.dispatch", 10 * MS, 2 * MS],
                ["mole.flush.fetch", 20 * MS, 3 * MS],
                ["mole.flush.fetch", 25 * MS, 4 * MS],
                ["mole.flush.device", 50 * MS, 10 * MS],
                ["mole.flush.dispatch", 50 * MS, 1 * MS],
                ["mole.flush.fetch", 55 * MS, 2 * MS],
                ["mole.flush.device", 70 * MS, 10 * MS],    # no fetch inside
                ["mole.flush.dispatch", 70 * MS, 4 * MS],
                ["mole.flush.fetch", 85 * MS, 1 * MS],      # in no device span
            ]},
            {"name": "event loop", "events": [
                ["mole.server.encode", 40 * MS, 6 * MS],
                ["mole.flush.fetch", 52 * MS, 5 * MS],      # another thread
                ["mole.server.encode", 99 * MS, 3 * MS],    # starts inside
                ["mole.server.encode", 100 * MS, 3 * MS],   # starts after it
            ]},
        ]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["k", 0.0, 12 * MS],
                ["k", 30 * MS, 12 * MS],
                ["k", 60 * MS, 40 * MS],
            ]},
        ]},
    ]}


def test_durations_of_spans_starting_inside_the_window():
    got = spans.durations_ms(synthetic(), WIN, "mole.server.encode")
    assert sorted(got) == [3.0, 6.0]
    assert spans.durations_ms(synthetic(), WIN, "mole.flush.wait") == []


def test_inner_spans_summed_per_outer_span_on_its_thread():
    got = spans.summed_within_ms(synthetic(), WIN, "mole.flush.device",
                                 "mole.flush.fetch")
    # Device spans at 10, 50 and 70 ms: the fetches at 20 and 25 ms; at 55
    # ms (the one at 52 ms is another thread's); none at 70 ms.  The device
    # span at -10 ms starts before the window.
    assert got == [7.0, 2.0, 0.0]


def _run(trace):
    return SimpleNamespace(trace={"plain": trace, "win": WIN})


def test_readers_take_the_median_and_find_nothing_without_spans():
    run = _run(synthetic())
    assert load_reader(ROOT, "flush_dispatch_ms.infer")(run) == 2.0
    assert load_reader(ROOT, "flush_fetch_ms.infer")(run) == 2.0
    assert load_reader(ROOT, "flush_fetch_ms.train")(run) == 2.0
    assert load_reader(ROOT, "result_encode_ms.train")(run) == 3.0
    # A program that opens no such span, and a run that was not traced.
    bare = {"planes": [p for p in synthetic()["planes"]
                       if p["name"].startswith("/device")]}
    for name in ("flush_dispatch_ms.infer", "flush_fetch_ms.train",
                 "result_encode_ms.train"):
        assert load_reader(ROOT, name)(_run(bare)) is None
        assert load_reader(ROOT, name)(SimpleNamespace(trace=None)) is None


def test_idle_device_time_under_spans():
    t = synthetic()
    # Idle: [12, 30) and [42, 60); busy from 60 ms to the window's end.
    assert tr.busy_s(t, WIN) == pytest.approx(0.064)
    # [42, 46) under the first encode span.
    got = spans.idle_under_s(t, WIN, ["mole.server.encode"])
    assert got == pytest.approx(0.004)
    # [12, 30) and [50, 60) under the device spans from 10 and 50 ms.
    got = spans.idle_by_span(t, WIN, ["mole.flush.device",
                                      "mole.server.encode", "mole.flush.fetch"])
    assert got["mole.flush.device"] == pytest.approx(0.028)
    # [20, 23), [25, 29) and [52, 57); the one at 85 ms is in busy time.
    assert got["mole.flush.fetch"] == pytest.approx(0.012)
    # Of 36 ms idle, [46, 50) lies under none of them.
    assert got["no program span"] == pytest.approx(0.004)


# -- the program's spans in a traced rehearsal ---------------------------------
NEW_METRICS = ("flush_dispatch_ms.infer", "flush_fetch_ms.infer",
               "flush_fetch_ms.train", "result_encode_ms.train")


def test_traced_rehearsal_holds_the_programs_spans(tmp_path, monkeypatch):
    from repro.runtime.tracing import SPANS

    root = tiny_root(tmp_path, "jnp")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            m["workloads"].append("tiny.closed")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    seen = []
    load = tr.load
    monkeypatch.setattr(tr, "load",
                        lambda path: seen.append(load(path)) or seen[-1])
    result = harness.run(root, "tiny.closed", 2 ** 33 + 5, 1.5, True,
                         started=time.perf_counter(), require_tpu=False)
    assert result["correct"] is True, result["check"]
    for name in NEW_METRICS:
        assert result["metrics"][name]["value"] > 0, name
    trace = seen[-1]
    win = tr.window(trace)
    # The tiny cell's path reaches every span the program opens.
    for name in SPANS:
        assert spans.durations_ms(trace, win, name), name
    # The device phase's three sub-spans nest inside it, on its thread.
    for line in spans._host_lines(trace):
        dev = spans._starts_inside(line["events"], "mole.flush.device", win)
        for sub in ("mole.flush.dispatch", "mole.flush.wait",
                    "mole.flush.fetch"):
            iv = spans._starts_inside(line["events"], sub, win)
            if not len(iv):
                continue
            assert len(dev), sub
            i = dev[:, 0].searchsorted(iv[:, 0], side="right") - 1
            assert (i >= 0).all(), sub
            assert (iv[:, 1] <= dev[i, 1]).all(), sub
    # The benchmark's own spans still wrap the program's, so the breakdown
    # puts idle time down to them as before.
    wrapped = spans.summed_within_ms(trace, win, "bench.flush.device",
                                     "mole.flush.device")
    assert sum(wrapped) == pytest.approx(
        sum(spans.durations_ms(trace, win, "mole.flush.device")))
