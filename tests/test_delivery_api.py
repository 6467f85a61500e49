"""The typed delivery front door (repro.runtime.api): DeliveryRequest
validation, DeliveryResult traces, deprecated-shim bit-identity, weighted
fair queueing, per-request deadlines, slot prefetch, and admission/stats
accounting."""
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ConvGeometry, LMSessionRegistry, SessionRegistry
from repro.runtime import (
    AsyncDeliveryEngine,
    DeliveryRequest,
    DeliveryResult,
    EngineStats,
    MoLeDeliveryEngine,
    RequestQueue,
    delivery_trace_count,
)

GEOM = ConvGeometry(alpha=2, beta=4, m=6, p=3)


def _registry(rng, tenants=3, kappa=2, capacity=None, weights=None):
    reg = SessionRegistry(GEOM, kappa=kappa, capacity=capacity)
    fan_in = GEOM.alpha * GEOM.p * GEOM.p
    for i in range(tenants):
        k = rng.standard_normal(
            (GEOM.alpha, GEOM.beta, GEOM.p, GEOM.p)
        ).astype(np.float32) / np.sqrt(fan_in)
        reg.register(
            f"t{i}", k, weight=weights[i] if weights else 1.0
        )
    return reg


def _data(rng, b=2):
    return rng.standard_normal((b, GEOM.alpha, GEOM.m, GEOM.m)).astype(
        np.float32
    )


# ---------------------------------------------------------------------------
# descriptor validation
# ---------------------------------------------------------------------------

def test_request_validates_lane_and_deliver():
    with pytest.raises(ValueError, match="lane"):
        DeliveryRequest("t0", None, lane="images")
    with pytest.raises(ValueError, match="deliver"):
        DeliveryRequest("t0", None, lane="tokens", deliver="logits")
    with pytest.raises(ValueError, match="only applies to lane='tokens'"):
        DeliveryRequest("t0", None, lane="rows", deliver="embed")


def test_request_validates_priority_and_deadline():
    with pytest.raises(ValueError, match="priority"):
        DeliveryRequest("t0", None, priority="high")
    with pytest.raises(ValueError, match="priority"):
        DeliveryRequest("t0", None, priority=True)
    with pytest.raises(ValueError, match="deadline_ms"):
        DeliveryRequest("t0", None, deadline_ms=0.0)
    with pytest.raises(ValueError, match="deadline_ms"):
        DeliveryRequest("t0", None, deadline_ms=-5)
    req = DeliveryRequest("t0", None, priority=-2, deadline_ms=1)
    assert req.deadline_ms == 1.0 and req.priority == -2


def test_request_is_frozen_and_snapshots_metadata():
    meta = {"trace_id": "abc"}
    req = DeliveryRequest("t0", None, metadata=meta)
    meta["trace_id"] = "mutated"            # caller's dict stays theirs
    assert req.metadata == {"trace_id": "abc"}
    with pytest.raises(AttributeError):
        req.priority = 3


def test_submit_rejects_request_plus_payload(rng):
    """The legacy (request, data) arity is gone outright: submit/deliver
    take exactly one descriptor, so a stray payload argument is a plain
    signature error."""
    eng = MoLeDeliveryEngine(_registry(rng, tenants=1))
    d = _data(rng)
    with pytest.raises(TypeError):
        eng.submit(DeliveryRequest("t0", d), d)
    with pytest.raises(TypeError):
        eng.deliver(DeliveryRequest("t0", d), d)


# ---------------------------------------------------------------------------
# DeliveryResult: payload + scheduling trace
# ---------------------------------------------------------------------------

def test_deliver_returns_result_with_trace(rng):
    reg = _registry(rng)
    eng = MoLeDeliveryEngine(reg)
    d = _data(rng, 3)
    res = eng.deliver(
        DeliveryRequest("t1", d, priority=7, metadata={"job": "42"})
    )
    assert isinstance(res, DeliveryResult)
    want = np.asarray(reg.session("t1").deliver(jnp.asarray(d)))
    np.testing.assert_allclose(res.payload, want, atol=1e-5)
    assert res.tenant_id == "t1" and res.lane == "rows" and res.priority == 7
    assert res.metadata == {"job": "42"}
    assert res.completed_at >= res.submitted_at and res.latency_ms >= 0.0
    assert res.queue_depth_at_submit == 0


def test_queue_depth_trace_counts_prior_backlog(rng):
    eng = MoLeDeliveryEngine(_registry(rng, tenants=1))
    r0 = eng.submit(DeliveryRequest("t0", _data(rng, 4)))
    r1 = eng.submit(DeliveryRequest("t0", _data(rng, 2)))
    eng.flush()
    assert eng.take_result(r0).queue_depth_at_submit == 0
    assert eng.take_result(r1).queue_depth_at_submit == 4


def test_take_returns_bare_payload_and_pops(rng):
    reg = _registry(rng, tenants=1)
    eng = MoLeDeliveryEngine(reg)
    d = _data(rng)
    rid = eng.submit(DeliveryRequest("t0", d))
    eng.flush()
    out = eng.take(rid)
    np.testing.assert_allclose(
        out, np.asarray(reg.session("t0").deliver(jnp.asarray(d))), atol=1e-5
    )
    with pytest.raises(KeyError, match="already taken"):
        eng.take_result(rid)


# ---------------------------------------------------------------------------
# removed legacy shims: the old spellings fail loudly, not silently
# ---------------------------------------------------------------------------

def test_legacy_spellings_are_gone(rng):
    """The deprecated ``submit(tenant, data)`` trio and its ``prepare_*``/
    ``deliver_*`` mirrors were removed after their deprecation cycle: the
    old positional spelling raises TypeError (not a silent mis-dispatch),
    and the per-lane methods no longer exist."""
    reg = SessionRegistry(GEOM, kappa=2)
    k = rng.standard_normal((GEOM.alpha, GEOM.beta, GEOM.p, GEOM.p)).astype(
        np.float32
    )
    reg.register("t0", k, seed=99)
    eng = MoLeDeliveryEngine(reg)
    d = _data(rng)

    with pytest.raises(TypeError):
        eng.submit("t0", d)           # legacy two-arg spelling
    with pytest.raises(TypeError, match="DeliveryRequest"):
        eng.submit("t0")              # untyped payload
    with pytest.raises(TypeError):
        eng.deliver("t0", d)
    for name in (
        "submit_tokens", "submit_features", "deliver_tokens",
        "deliver_features", "prepare_rows", "prepare_tokens",
        "prepare_features",
    ):
        assert not hasattr(eng, name)

    with AsyncDeliveryEngine(reg, max_delay_ms=5.0) as front:
        res = front.submit(DeliveryRequest("t0", d)).result(timeout=60)
        assert isinstance(res, DeliveryResult)   # typed path: full result
        with pytest.raises(TypeError):
            front.submit("t0", d)     # legacy two-arg spelling
        with pytest.raises(TypeError, match="DeliveryRequest"):
            front.submit("t0")        # untyped payload
        with pytest.raises(TypeError):
            front.deliver("t0", d)
        for name in ("submit_tokens", "submit_features", "deliver_tokens"):
            assert not hasattr(front, name)


# ---------------------------------------------------------------------------
# weighted fair queueing: cross-tenant shares
# ---------------------------------------------------------------------------

def test_weight2_tenant_gets_double_goodput_under_saturation(rng):
    """The acceptance property: saturated identical backlogs, bounded flush
    rounds — the weight-2 tenant completes ~2x the weight-1 tenant's rows."""
    reg = _registry(rng, tenants=2, capacity=2, weights=(2.0, 1.0))
    eng = MoLeDeliveryEngine(
        reg, max_rows=4, row_buckets=(1, 2, 4), group_buckets=(1, 2),
        max_flush_microbatches=2,
    )
    datas = {}
    for _ in range(24):
        for t in ("t0", "t1"):
            d = _data(rng, 4)
            datas[eng.submit(DeliveryRequest(t, d))] = (t, d)
    served = {"t0": 0, "t1": 0}
    for _ in range(6):                   # 6 rounds x 2 microbatches x 2 groups
        work = eng.begin_flush()
        assert work is not None          # still saturated
        eng.execute_flush(work)
        for rid, out in eng.publish_flush(work).items():
            t, d = datas[rid]
            served[t] += d.shape[0]
            want = np.asarray(reg.session(t).deliver(jnp.asarray(d)))
            np.testing.assert_allclose(out, want, atol=1e-5)
    ratio = served["t0"] / served["t1"]
    assert 1.6 <= ratio <= 2.6, served


def test_registry_weight_validation(rng):
    reg = _registry(rng, tenants=1)
    assert reg.weight_of("t0") == 1.0 and reg.weight_of("ghost") == 1.0
    reg.set_weight("t0", 3.0)
    assert reg.weight_of("t0") == 3.0
    with pytest.raises(KeyError):
        reg.set_weight("ghost", 2.0)
    with pytest.raises(ValueError):
        reg.set_weight("t0", 0.0)
    with pytest.raises(ValueError):
        RequestQueue(4).submit("a", np.ones((1, 4), np.float32), weight=-1.0)


def test_idle_tenant_banks_no_wfq_credit():
    """A tenant idle for many rounds re-enters at the global virtual time:
    it cannot starve the active tenant with accumulated credit."""
    q = RequestQueue(4, max_rows=4, row_buckets=(1, 2, 4), group_buckets=(1, 2))
    rows = np.ones((4, 4), np.float32)
    for _ in range(10):                  # "a" alone consumes many rounds
        q.submit("a", rows)
        q.coalesce({"a": 0, "b": 1})
    q.submit("a", rows)
    q.submit("a", rows)
    q.submit("b", rows)                  # b wakes after a long idle spell
    mb = q.coalesce({"a": 0, "b": 1})
    # b gets exactly one fair chunk of the 2-group microbatch, not the whole
    # backlog's worth of catch-up service
    by_tenant = {0: 0, 1: 0}
    for s in mb.slices:
        by_tenant[int(mb.group_tenant[s.group])] += s.n_rows
    assert by_tenant == {0: 4, 1: 4}


def test_saturating_vision_backlog_does_not_starve_lm_lane(rng):
    """begin_flush round-robins its microbatch cap across lanes: a vision
    backlog many times deeper than one bounded round still leaves the token
    lane a slot in the very first round."""
    vreg = _registry(rng, tenants=1)
    lreg = LMSessionRegistry(67, 4, capacity=1)
    lreg.register("lm0", rng.standard_normal((67, 4)).astype(np.float32),
                  seed=3)
    eng = MoLeDeliveryEngine(
        vreg, lm_registry=lreg, max_rows=4, row_buckets=(1, 2, 4),
        group_buckets=(1, 2), max_flush_microbatches=2,
    )
    for _ in range(16):   # ~8 bounded rounds of vision backlog
        eng.submit(DeliveryRequest("t0", _data(rng, 4)))
    toks = rng.integers(0, 67, (1, 5))
    rid = eng.submit(DeliveryRequest("lm0", toks, lane="tokens"))
    work = eng.begin_flush()          # ONE bounded round
    assert {item.lane for item in work.items} == {"vision", "tokens"}
    eng.execute_flush(work)
    assert rid in eng.publish_flush(work)
    np.testing.assert_array_equal(
        eng.take(rid),
        np.asarray(lreg.session("lm0").morph_tokens(jnp.asarray(toks))),
    )


def test_idle_lanes_pruned_once_clock_catches_up():
    """Lane records of long-idle tenants are dropped once the global virtual
    clock passes their vtime (re-entry resolves identically), so _lanes is
    bounded by recently active tenants, not every tenant ever seen."""
    q = RequestQueue(4, max_rows=4, row_buckets=(1, 2, 4), group_buckets=(1, 2))
    q.submit("a", np.ones((12, 4), np.float32))   # 3 chunks of backlog
    q.submit("b", np.ones((4, 4), np.float32))    # 1 chunk, then idle
    q.coalesce({"a": 0, "b": 1})   # serves a + b (both reach vtime 4)
    q.coalesce({"a": 0, "b": 1})   # serves a twice: clock advances to 8
    assert "b" not in q._lanes     # idle, vtime 4 <= clock: pruned
    assert "a" in q._lanes         # still carries debt (vtime 12 > clock)
    # a re-submitting b behaves exactly as the never-pruned idle re-entry
    q.submit("b", np.ones((2, 4), np.float32))
    assert q._lanes["b"].vtime == q._vnow


def test_explicit_weight_survives_idle_prune():
    """A standalone queue user's weight=... persists across the tenant's
    idle spells (and the idle-lane prune) without re-passing it."""
    q = RequestQueue(4, max_rows=4, row_buckets=(1, 2, 4), group_buckets=(1, 2))
    rows = np.ones((4, 4), np.float32)
    q.submit("a", rows, weight=4.0)
    q.submit("b", rows)
    # drain + advance the clock past both lanes so the prune fires
    while q.coalesce({"a": 0, "b": 1}) is not None:
        pass
    q.submit("b", rows)
    q.submit("b", rows)
    while q.coalesce({"a": 0, "b": 1}) is not None:
        pass
    assert "a" not in q._lanes
    q.submit("a", rows)                    # wakes with no weight= passed
    assert q._lanes["a"].weight == 4.0
    q.submit("a", rows, weight=1.0)        # back to default: forgotten
    assert q._weights == {}


# ---------------------------------------------------------------------------
# per-request deadlines in the async flusher
# ---------------------------------------------------------------------------

def test_tight_request_deadline_flushes_before_engine_slo(rng):
    """A request's deadline_ms far below max_delay_ms triggers the flush —
    the engine-wide SLO alone would sit on it for a minute."""
    reg = _registry(rng, tenants=1)
    with AsyncDeliveryEngine(reg, max_delay_ms=60_000.0) as front:
        d = _data(rng)
        # warm the compile cache (itself via a tight deadline — a default
        # request would sit on the 60 s engine SLO)
        front.deliver(DeliveryRequest("t0", d, deadline_ms=20.0), timeout=60)
        t0 = time.monotonic()
        res = front.submit(
            DeliveryRequest("t0", d, deadline_ms=20.0)
        ).result(timeout=60)
        wall_s = time.monotonic() - t0
        np.testing.assert_allclose(
            res.payload,
            np.asarray(reg.session("t0").deliver(jnp.asarray(d))), atol=1e-5,
        )
        assert wall_s < 30.0             # nowhere near the 60 s SLO


def test_looser_request_deadline_does_not_block_tight_neighbours(rng):
    """Mixed deadlines in one queue: the heap orders by absolute deadline,
    so a tight request behind a loose one still flushes on time (and the
    loose one simply rides along in the same flush)."""
    reg = _registry(rng, tenants=2)
    with AsyncDeliveryEngine(reg, max_delay_ms=60_000.0) as front:
        d = _data(rng)
        for t in reg.tenant_ids:   # warm (tight deadlines: 60 s engine SLO)
            front.deliver(DeliveryRequest(t, d, deadline_ms=20.0), timeout=60)
        f_loose = front.submit(DeliveryRequest("t0", d, deadline_ms=50_000.0))
        f_tight = front.submit(DeliveryRequest("t1", d, deadline_ms=20.0))
        res = f_tight.result(timeout=30)
        assert res.tenant_id == "t1"
        assert f_loose.result(timeout=30)  # same flush drained it


def test_warm_deliver_with_default_deadline_meets_engine_slo(rng):
    reg = _registry(rng, tenants=1)
    with AsyncDeliveryEngine(reg, max_delay_ms=25.0) as front:
        d = _data(rng)
        front.deliver(DeliveryRequest("t0", d), timeout=60)  # warm
        t0 = time.monotonic()
        front.deliver(DeliveryRequest("t0", d), timeout=60)
        assert (time.monotonic() - t0) < 0.025 + 0.75  # SLO + CI slack


# ---------------------------------------------------------------------------
# admission accounting + stats degradation
# ---------------------------------------------------------------------------

def test_admission_accounting_per_tenant(rng):
    from repro.runtime import AdmissionError

    reg = _registry(rng, tenants=2)
    front = AsyncDeliveryEngine(
        reg, max_delay_ms=60_000.0, max_inflight_rows=3, admission="reject"
    )
    try:
        d = _data(rng, 2)
        f0 = front.submit(DeliveryRequest("t0", d))
        with pytest.raises(AdmissionError):
            front.submit(DeliveryRequest("t0", d))
        with pytest.raises(AdmissionError):
            front.submit(DeliveryRequest("t0", d))
        front.submit(DeliveryRequest("t1", d))
        assert front.stats.rejected == 2
        assert front.stats.rejected_by_tenant == {"t0": 2}
        assert "rejects_by_tenant" in front.stats.summary()
        front.flush_now()
        f0.result(timeout=60)
    finally:
        front.close()


def test_stats_summary_degrades_without_samples():
    s = EngineStats().summary()
    assert "n/a" in s and "nan" not in s
    assert "admission" in s and "wfq virtual-time lag" in s


def test_per_priority_latency_quantiles(rng):
    reg = _registry(rng, tenants=1)
    eng = MoLeDeliveryEngine(reg)
    d = _data(rng, 1)
    for prio in (0, 0, 5):
        eng.deliver(DeliveryRequest("t0", d, priority=prio))
    stats = eng.stats
    assert stats.priorities_seen == (5, 0)
    for prio in (0, 5):
        p50 = stats.latency_quantile_ms(0.5, priority=prio)
        assert p50 == p50 and p50 >= 0.0
    assert "priority   5" in stats.summary()
    # a never-seen priority reads as NaN, not KeyError
    nan = stats.latency_quantile_ms(0.5, priority=9)
    assert nan != nan


def test_padding_groups_skipped_counts_the_padding_groups(rng):
    """A microbatch of 3 of 4 tenants buckets to G = 4: the rows step is
    told one group is padding, and the engine counts it next to the
    clamps."""
    reg = _registry(rng, tenants=4, capacity=4)
    eng = MoLeDeliveryEngine(reg, group_buckets=(1, 2, 4))
    datas = {t: _data(rng, 1) for t in reg.tenant_ids[:3]}
    for t, d in datas.items():
        eng.submit(DeliveryRequest(t, d))
    work = eng.begin_flush()
    (item,) = work.items
    assert (item.mb.x.shape[0], item.mb.n_real_groups) == (4, 3)
    assert eng.stats.padding_groups_skipped == 1
    eng.execute_flush(work)
    done = eng.publish_flush(work)
    assert "padding_clamps=0 padding_groups_skipped=1" in eng.stats.summary()
    for s in item.mb.slices:
        tenant = reg.tenant_ids[int(item.mb.group_tenant[s.group])]
        want = np.asarray(reg.session(tenant).deliver(
            jnp.asarray(datas[tenant])))
        np.testing.assert_allclose(done[s.request_id], want, atol=1e-5)


def test_padding_clamp_count_surfaces_in_stats(rng):
    """Coalescing a G bucket past max_groups clamps padding indices — the
    engine must count it (padding_clamp_count) instead of staying silent."""
    q = RequestQueue(4, max_rows=8, row_buckets=(1, 2, 4, 8),
                     group_buckets=(1, 2, 4))
    for tenant in ("a", "b", "c"):
        q.submit(tenant, np.ones((1, 4), np.float32))
    mb = q.coalesce({"a": 0, "b": 1, "c": 2}, max_groups=3)
    assert list(mb.group_tenant) == [0, 1, 2, 2]
    assert mb.n_clamped_padding == 1
    # Engine path: the ensured capacity bucket normally makes clamping
    # unreachable (G never buckets past max_groups) — the counter is a
    # tripwire.  Simulate the regression it guards against by dropping the
    # ensured bucket, and the flush must surface the clamp in the stats.
    reg = _registry(rng, tenants=3, capacity=3)
    eng = MoLeDeliveryEngine(reg, group_buckets=(1, 2, 4))
    assert eng.stats.padding_clamp_count == 0
    for t in reg.tenant_ids:
        eng.submit(DeliveryRequest(t, _data(rng, 1)))
    eng._refresh_plan()                       # would ensure the 3-bucket...
    eng.queue.group_buckets = (1, 2, 4)       # ...regress it away
    eng.flush()
    assert eng.stats.padding_clamp_count == 1
    assert "padding_clamps=1" in eng.stats.summary()


# ---------------------------------------------------------------------------
# slot prefetch
# ---------------------------------------------------------------------------

def test_prefetch_activates_evicted_tenants_off_critical_path(rng):
    reg = _registry(rng, tenants=4, capacity=2)
    eng = MoLeDeliveryEngine(reg)
    d = _data(rng)
    eng.deliver(DeliveryRequest("t0", d))
    eng.deliver(DeliveryRequest("t1", d))   # t0, t1 resident; t2, t3 evicted
    slots = eng.prefetch(["t2", "t3"])
    assert set(slots) == {"t2", "t3"}
    assert reg.is_resident("t2") and reg.is_resident("t3")
    assert not reg.is_resident("t0") and not reg.is_resident("t1")
    # secrets are already staged: the device plan is current, so the next
    # flush's re-sync has nothing to copy
    assert eng._plan.version == reg.version
    res = eng.deliver(DeliveryRequest("t2", d))
    np.testing.assert_allclose(
        res.payload, np.asarray(reg.session("t2").deliver(jnp.asarray(d))),
        atol=1e-5,
    )


def test_prefetch_interacts_with_lru_like_use(rng):
    """Prefetch touches the LRU clock: freshly prefetched tenants are the
    most recently used, so over-capacity prefetch keeps the *last* ones and
    a subsequent activation evicts the coldest tenant, not a prefetched one."""
    reg = _registry(rng, tenants=4, capacity=2)
    eng = MoLeDeliveryEngine(reg)
    # over-capacity prefetch: the last `capacity` survive
    eng.prefetch(["t0", "t1", "t2"])
    assert not reg.is_resident("t0")
    assert reg.is_resident("t1") and reg.is_resident("t2")
    evictions = reg.evictions
    # activating t3 evicts t1 (oldest touch), keeping the fresher t2
    reg.slot_for("t3")
    assert not reg.is_resident("t1") and reg.is_resident("t2")
    assert reg.evictions == evictions + 1
    with pytest.raises(KeyError):
        eng.prefetch(["nobody"])


def test_prefetch_does_not_retrace(rng):
    reg = _registry(rng, tenants=4, capacity=2)
    eng = MoLeDeliveryEngine(reg)
    d = _data(rng)
    eng.deliver(DeliveryRequest("t0", d))       # compiles the bucket
    n0 = delivery_trace_count()
    eng.prefetch(["t2", "t3"])                  # churn via prefetch
    eng.deliver(DeliveryRequest("t2", d))
    assert delivery_trace_count() == n0


def test_async_prefetch_under_lock(rng):
    reg = _registry(rng, tenants=3, capacity=2)
    with AsyncDeliveryEngine(reg, max_delay_ms=5.0) as front:
        slots = front.prefetch(["t2"])
        assert reg.is_resident("t2") and "t2" in slots
        d = _data(rng)
        res = front.submit(DeliveryRequest("t2", d)).result(timeout=60)
        np.testing.assert_allclose(
            res.payload,
            np.asarray(reg.session("t2").deliver(jnp.asarray(d))), atol=1e-5,
        )


# ---------------------------------------------------------------------------
# zero-retrace acceptance under the new scheduler
# ---------------------------------------------------------------------------

def test_mixed_priority_and_churn_do_not_retrace(rng):
    """The PR acceptance: priorities, weights, and tenant churn are pure
    host-side scheduling — the jitted device steps never retrace at a fixed
    (bucket, kappa, backend) shape."""
    reg = _registry(rng, tenants=4, capacity=4, weights=(2.0, 1.0, 1.0, 1.0))
    eng = MoLeDeliveryEngine(reg)
    d = _data(rng, 3)

    def roundtrip(prios):
        rids = {
            t: eng.submit(DeliveryRequest(t, d, priority=p))
            for t, p in zip(reg.tenant_ids[:4], prios)
        }
        eng.flush()
        for t, rid in rids.items():
            want = np.asarray(reg.session(t).deliver(jnp.asarray(d)))
            np.testing.assert_allclose(eng.take(rid), want, atol=1e-5)

    roundtrip((0, 0, 0, 0))                 # compiles the bucket
    n0 = delivery_trace_count()
    roundtrip((3, -1, 0, 2))                # mixed priorities: same bucket
    reg.set_weight("t1", 4.0)               # weight change mid-stream
    roundtrip((1, 1, 0, 0))
    k = rng.standard_normal((GEOM.alpha, GEOM.beta, GEOM.p, GEOM.p)).astype(
        np.float32
    )
    reg.register("t4", k)                   # churn: eviction at capacity
    eng.deliver(DeliveryRequest("t4", d, priority=5))
    roundtrip((0, 2, 0, 1))                 # re-activation churn
    assert delivery_trace_count() == n0
