"""Flush rounds two deep (repro.runtime.async_engine): round n publishes on
the publisher thread, its scatter off the front door's lock, while round
n+1 runs its device phase.  Results stay the sync engine's bit for bit,
each tenant's requests complete in order, decode rounds never overlap, a
crash with two rounds in flight answers every request exactly once, and the
lock is free during the scatter."""
import collections
import os
import sys
import threading
import time

import numpy as np
import pytest

from repro.core import ConvGeometry, SessionRegistry
from repro.launch.serve import parse_args
from repro.launch.server import build_front
from repro.runtime import (
    AsyncDeliveryEngine,
    DeliveryRequest,
    MoLeDeliveryEngine,
    SimulatedFailure,
)

GEOM = ConvGeometry(alpha=2, beta=4, m=6, p=3)
# Four-row microbatches of one tenant, one a round: a 3-row request's rows
# span two rounds whenever it does not start a microbatch.
SMALL = dict(max_rows=4, row_buckets=(1, 2, 4), group_buckets=(1,),
             max_flush_microbatches=1)


def _registry(rng, tenants=3):
    reg = SessionRegistry(GEOM, kappa=2)
    fan_in = GEOM.alpha * GEOM.p * GEOM.p
    for i in range(tenants):
        reg.register(f"t{i}", rng.standard_normal(
            (GEOM.alpha, GEOM.beta, GEOM.p, GEOM.p)
        ).astype(np.float32) / np.sqrt(fan_in))
    return reg


def _images(rng, n):
    return rng.standard_normal((n, GEOM.alpha, GEOM.m, GEOM.m)).astype(
        np.float32)


class _SlowScatter(MoLeDeliveryEngine):
    """Each scatter takes a few milliseconds, so the next round is due and
    dispatched while it runs."""

    def _scatter_rows(self, *args):
        time.sleep(0.004)
        return super()._scatter_rows(*args)


class _GatedScatter(MoLeDeliveryEngine):
    """The first scatter blocks until ``gate`` is set; counts device
    phases."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.gate = threading.Event()
        self.in_scatter = threading.Event()
        self.executed = 0
        self.second_executed = threading.Event()

    def _scatter_rows(self, *args):
        if not self.in_scatter.is_set():
            self.in_scatter.set()
            assert self.gate.wait(timeout=60), "test never opened the gate"
        return super()._scatter_rows(*args)

    def execute_flush(self, work):
        super().execute_flush(work)
        self.executed += 1
        if self.executed == 2:
            self.second_executed.set()


def test_results_equal_the_sync_engine_bit_for_bit(rng):
    """Rows-lane results through overlapped rounds, including requests
    whose rows span two rounds, are the sync engine's bit for bit, and each
    tenant's requests complete in the order they were submitted."""
    reg = _registry(rng)
    reqs = [(f"t{i % 3}", _images(rng, 3)) for i in range(18)]
    sync = MoLeDeliveryEngine(reg, **SMALL)
    rids = [sync.submit(DeliveryRequest(t, d)) for t, d in reqs]
    sync.flush()
    want = [sync.take(r) for r in rids]

    eng = _SlowScatter(reg, **SMALL)
    with AsyncDeliveryEngine(eng, max_delay_ms=1.0, flush_rows=1) as front:
        futs = [front.submit(DeliveryRequest(t, d)) for t, d in reqs]
        got = [f.result(timeout=60) for f in futs]
        front.drain(timeout=60)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g.payload, w)
    # Completion stamps are taken at each round's commit, in order.
    for tenant in reg.tenant_ids:
        done = [g.completed_at for g in got if g.tenant_id == tenant]
        assert len(done) == 6 and done == sorted(done), tenant
    # 54 rows in 4-row rounds: most rows shared a round with another
    # request's, and rounds ran two deep.
    assert eng.stats.flushes >= 14
    assert 1 <= eng.stats.rounds_overlapped < eng.stats.flushes
    assert not eng._partial and not eng._results


def test_many_submitters_under_fast_thread_switching(rng):
    """More submitting threads than cores, the interpreter switching
    threads every few microseconds: every request is answered once with
    its own result, and the admission accounting returns to zero."""
    reg = _registry(rng)
    n_threads, per_thread = 4 * (os.cpu_count() or 1), 6
    data = {t: _images(rng, 3) for t in reg.tenant_ids}
    want = {t: np.asarray(reg.session(t).deliver(d)) for t, d in data.items()}
    answers, counted = collections.Counter(), threading.Lock()
    futs, errors = [], []

    def count(f):
        with counted:
            answers[f.request_id] += 1

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with AsyncDeliveryEngine(_SlowScatter(reg, **SMALL), max_delay_ms=1.0,
                                 flush_rows=1) as front:
            def submit(k):
                try:
                    for j in range(per_thread):
                        t = f"t{(k + j) % 3}"
                        f = front.submit(DeliveryRequest(t, data[t]))
                        f.add_done_callback(count)
                        futs.append((t, f))
                except BaseException as e:  # pragma: no cover - surfaced
                    errors.append(e)

            threads = [threading.Thread(target=submit, args=(k,))
                       for k in range(n_threads)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=60)
            assert not any(th.is_alive() for th in threads) and not errors
            for t, f in futs:
                np.testing.assert_array_equal(f.result(timeout=60).payload,
                                              want[t])
            front.drain(timeout=60)
            assert front.inflight_rows() == 0 and front.pending() == 0
            assert not front.engine._partial and not front.engine._results
    finally:
        sys.setswitchinterval(switch)
    assert len(futs) == n_threads * per_thread
    assert sorted(answers.values()) == [1] * len(futs)


def test_rounds_overlapped_counts_rounds_dispatched_during_a_publish(rng):
    """While round 1's scatter is held, a due round 2 is coalesced and its
    device phase runs: that one round counts; round 3, dispatched after
    everything published, does not.  The front door's lock is free during
    the scatter: a submit and an inflight_rows from another thread return
    before it ends."""
    reg = _registry(rng)
    eng = _GatedScatter(reg, **SMALL)
    front = AsyncDeliveryEngine(eng, max_delay_ms=60_000.0, flush_rows=1)
    try:
        d1, d2, d3 = _images(rng, 2), _images(rng, 2), _images(rng, 1)
        f1 = front.submit(DeliveryRequest("t0", d1))
        assert eng.in_scatter.wait(timeout=30)
        f2 = front.submit(DeliveryRequest("t1", d2))
        assert eng.second_executed.wait(timeout=30)   # round 2 ran
        assert not f1.done() and not f2.done()

        other = {}

        def touch():
            other["fut"] = front.submit(DeliveryRequest("t2", d3))
            other["rows"] = front.inflight_rows()

        th = threading.Thread(target=touch)
        th.start()
        th.join(timeout=30)
        assert not th.is_alive(), "the lock was held during the scatter"
        assert other["rows"] == 5 and not f1.done()
        assert eng.stats.rounds_overlapped == 1
        eng.gate.set()
        for f, (t, d) in ((f1, ("t0", d1)), (f2, ("t1", d2)),
                          (other["fut"], ("t2", d3))):
            want = reg.session(t).deliver(d)
            np.testing.assert_array_equal(f.result(timeout=60).payload, want)
        front.drain(timeout=60)
        # Round 3 may start while round 2 still publishes.
        assert eng.stats.flushes == 3
        assert eng.stats.rounds_overlapped in (1, 2)
        assert "rounds_overlapped=" in eng.stats.summary()
    finally:
        eng.gate.set()
        front.close()


class _SecondRoundInjector:
    """Raises SimulatedFailure once, while a second round is in flight: at
    the second coalesce or device phase (round 1's scatter held open), or
    at round 1's publish once round 2 has run its device phase."""

    def __init__(self, phase: str, engine):
        self.phase, self.engine = phase, engine
        self.calls = collections.Counter()
        self.fired = threading.Event()

    def maybe_fail_phase(self, phase: str) -> None:
        self.calls[phase] += 1
        if phase != self.phase or self.fired.is_set():
            return
        if phase == "publish":
            assert self.engine.second_executed.wait(timeout=30)
        elif self.calls[phase] < 2:
            return
        self.fired.set()
        raise SimulatedFailure(f"injected at {phase}")


@pytest.mark.parametrize("phase", ["coalesce", "device", "publish"])
def test_a_crash_with_two_rounds_in_flight_answers_each_request_once(
        rng, phase):
    reg = _registry(rng)
    eng = _GatedScatter(reg, **SMALL)
    eng.injector = _SecondRoundInjector(phase, eng)
    if phase == "publish":
        eng.gate.set()        # the failure comes before any scatter
    answers, counted = collections.Counter(), threading.Lock()

    def count(f):
        with counted:
            answers[f.request_id] += 1

    front = AsyncDeliveryEngine(eng, max_delay_ms=60_000.0, flush_rows=1)
    try:
        reqs = [("t0", _images(rng, 3)), ("t1", _images(rng, 2))]
        futs = []
        for t, d in reqs:
            f = front.submit(DeliveryRequest(t, d))
            f.add_done_callback(count)
            futs.append(f)
            if len(futs) == 1 and phase != "publish":
                assert eng.in_scatter.wait(timeout=30)
        assert eng.injector.fired.wait(timeout=30)
        eng.gate.set()
        for f, (t, d) in zip(futs, reqs):
            np.testing.assert_array_equal(f.result(timeout=60).payload,
                                          reg.session(t).deliver(d))
        front.drain(timeout=60)
        assert front._restarts == 1
        assert sorted(answers.values()) == [1, 1]
        with front._cv:
            assert not eng._results and not eng._partial and not eng._done
    finally:
        eng.gate.set()
        front.close()


def test_a_round_with_a_decode_step_is_never_overlapped():
    """LM tenants generating while a tokens-lane stream runs: rounds that
    carry a decode step are neither coalesced nor dispatched before the
    previous round has published, and only rounds without one count as
    overlapped."""
    args = parse_args([
        "--mode", "serve", "--port", "0", "--arch", "deepseek_v2_lite_16b",
        "--smoke", "--tenants", "2", "--rows", "4", "--max-len", "64",
        "--prompt-buckets", "8,16,32", "--backend", "jnp", "--seed", "7",
    ])
    front = build_front(args)
    eng = front.engine
    events, lock = [], threading.Lock()

    def logged(name, fn):
        def wrapped(work, *a, **k):
            with lock:
                events.append((name + "<", id(work), work.serial))
            try:
                return fn(work, *a, **k)
            finally:
                with lock:
                    events.append((name + ">", id(work), work.serial))
        return wrapped

    eng.execute_flush = logged("execute", eng.execute_flush)
    eng.publish_flush = logged("publish", eng.publish_flush)
    begin = eng.begin_flush

    def logged_begin():
        work = begin()
        if work is not None:
            with lock:
                events.append(("begin", id(work), work.serial))
        return work

    eng.begin_flush = logged_begin
    rng = np.random.default_rng(11)
    try:
        futs = []
        for i in range(12):
            if i % 4 == 0:
                futs.append(front.submit(DeliveryRequest(
                    f"tenant-{i % 8 // 4}",
                    rng.integers(0, 512, 6).astype(np.int32),
                    lane="decode", max_new_tokens=6)))
            futs.append(front.submit(DeliveryRequest(
                "tenant-0", rng.integers(0, 512, (2, 8)).astype(np.int32),
                lane="tokens")))
            time.sleep(0.002)
        for f in futs:
            f.result(timeout=120)
        front.drain(timeout=60)
    finally:
        front.close()
    published, serial_rounds = set(), 0
    begun = []
    for name, wid, serial in events:
        if name == "begin":
            if begun and begun[-1][1]:
                assert begun[-1][0] in published, "coalesced ahead of decode"
            begun.append((wid, serial))
        elif name == "execute<" and serial:
            serial_rounds += 1
            assert all(w in published for w, _ in begun if w != wid), (
                "a decode round dispatched ahead of a publish")
        elif name == "publish>":
            published.add(wid)
    assert serial_rounds >= 3
    overlappable = sum(1 for _, serial in begun if not serial)
    assert eng.stats.rounds_overlapped <= overlappable
