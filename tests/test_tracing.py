"""The served path's own host spans (repro.runtime.tracing) and the timing
that rides on them: EngineStats' flush phases and per-request queue waits."""
import contextlib
import re
import time
from pathlib import Path

import numpy as np
import pytest

from repro.core import ConvGeometry, SessionRegistry
from repro.runtime import DeliveryRequest, MoLeDeliveryEngine, engine, tracing

GEOM = ConvGeometry(alpha=2, beta=4, m=6, p=3)
SRC = Path(__file__).resolve().parents[1] / "src" / "repro"


def _registry(rng, tenants=2):
    reg = SessionRegistry(GEOM, kappa=2)
    for i in range(tenants):
        reg.register(f"t{i}", rng.standard_normal(
            (GEOM.alpha, GEOM.beta, GEOM.p, GEOM.p)).astype(np.float32))
    return reg


def _images(rng, n):
    return rng.standard_normal((n, GEOM.alpha, GEOM.m, GEOM.m)).astype(
        np.float32)


def test_every_span_the_program_opens_is_listed_once():
    opened = set()
    for path in SRC.rglob("*.py"):
        opened |= set(re.findall(r'\bspan\("([^"]+)"\)', path.read_text()))
    opened |= set(engine._PHASE_SPANS.values())
    listed = tracing.SPANS + tracing.DECODE_SPANS
    assert len(set(listed)) == len(listed)
    assert opened == set(listed)
    assert all(n.startswith("mole.") for n in listed)


@pytest.fixture
def recorded(monkeypatch):
    """Every span the engine opens, as (name, start, end), in order of end."""
    events = []

    @contextlib.contextmanager
    def recording(name):
        t0 = time.monotonic()
        yield
        events.append((name, t0, time.monotonic()))

    monkeypatch.setattr(engine, "span", recording)
    return events


def test_device_phase_covers_its_sub_spans(rng, recorded):
    """The device phase's reservoir still times the whole of execute_flush's
    work: every item's dispatch, wait and fetch lie inside it."""
    eng = MoLeDeliveryEngine(_registry(rng), max_rows=4,
                             row_buckets=(1, 2, 4), group_buckets=(1, 2))
    for t in ("t0", "t1", "t0"):
        eng.submit(DeliveryRequest(t, _images(rng, 3)))
    eng.flush()
    device = [e for e in recorded if e[0] == "mole.flush.device"]
    assert len(device) == eng.stats.flushes == 1
    _, lo, hi = device[0]
    inner = [e for e in recorded
             if e[0] in ("mole.flush.dispatch", "mole.flush.wait",
                         "mole.flush.fetch")]
    # One dispatch for the flush, one wait and one fetch per microbatch.
    names = [n for n, _, _ in inner]
    assert names[0] == "mole.flush.dispatch"
    assert names[1:] == ["mole.flush.wait", "mole.flush.fetch"] * (
        eng.stats.microbatches)
    assert all(lo <= s <= e <= hi for _, s, e in inner)
    (device_ms,) = eng.stats._phases_ms["device"]
    assert device_ms >= sum(e - s for _, s, e in inner) * 1e3
    # The other phases keep their reservoirs and spans.
    for phase in ("coalesce", "publish"):
        assert len(eng.stats._phases_ms[phase]) == 1
        assert [n for n, _, _ in recorded].count(f"mole.flush.{phase}") >= 1


def test_an_empty_coalesce_is_not_a_flush(rng):
    eng = MoLeDeliveryEngine(_registry(rng))
    assert eng.begin_flush() is None
    assert eng.stats.flushes == 0 and not eng.stats._phases_ms["coalesce"]


def test_a_failed_phase_records_nothing(rng, monkeypatch):
    eng = MoLeDeliveryEngine(_registry(rng))
    eng.submit(DeliveryRequest("t0", _images(rng, 1)))
    work = eng.begin_flush()
    monkeypatch.setattr(eng, "_dispatch", lambda item: 1 / 0)
    with pytest.raises(ZeroDivisionError):
        eng.execute_flush(work)
    assert not eng.stats._phases_ms["device"]


def test_each_request_records_one_queue_wait(rng):
    """One queue wait per request, at the coalesce that takes its first
    rows: a request split across microbatches and flush rounds counts once,
    and so does one replayed after a crash."""
    eng = MoLeDeliveryEngine(_registry(rng), max_rows=4,
                             row_buckets=(1, 2, 4), group_buckets=(1,),
                             max_flush_microbatches=1)
    big = eng.submit(DeliveryRequest("t0", _images(rng, 10)))
    assert eng.begin_flush() is not None
    eng.requeue_inflight()           # the round is lost after its coalesce
    assert len(eng.stats._queue_wait_ms) == 1
    small = eng.submit(DeliveryRequest("t1", _images(rng, 2)))
    time.sleep(0.02)
    done = eng.flush()
    assert set(done) == {big, small}
    assert eng.stats.microbatches >= 4   # 10 rows in 4-row microbatches
    waits = list(eng.stats._queue_wait_ms)
    assert len(waits) == 2
    # The second request waited at least the sleep; none waited negatively.
    assert waits[1] >= 20.0 and waits[0] >= 0.0
    assert eng.stats.queue_wait_quantile_ms(0.5) in waits
    assert "queue wait: p50=" in eng.stats.summary()
