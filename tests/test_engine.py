"""Delivery engine (repro.runtime.engine): multi-tenant isolation, padded
microbatch equivalence to per-request delivery, and kernel backend dispatch."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ConvGeometry, SessionRegistry, morph
from repro.core.morphing import unmorph
from repro.kernels import morph_rows_batched, aug_conv_forward_batched, ref
from repro.kernels.dispatch import resolve_backend
from repro.runtime import (
    DeliveryRequest,
    MoLeDeliveryEngine,
    RequestQueue,
    delivery_trace_count,
)


GEOM = ConvGeometry(alpha=2, beta=4, m=6, p=3)


def _sub(eng, tenant, data, **kw):
    """Typed-front-door submit (the shim spelling is covered in
    tests/test_delivery_api.py)."""
    return eng.submit(DeliveryRequest(tenant, data, **kw))


def _del(eng, tenant, data, **kw):
    return eng.deliver(DeliveryRequest(tenant, data, **kw)).payload


def _registry(rng, tenants=3, kappa=2, capacity=None):
    reg = SessionRegistry(GEOM, kappa=kappa, capacity=capacity)
    fan_in = GEOM.alpha * GEOM.p * GEOM.p
    for i in range(tenants):
        k = rng.standard_normal(
            (GEOM.alpha, GEOM.beta, GEOM.p, GEOM.p)
        ).astype(np.float32) / np.sqrt(fan_in)
        reg.register(f"t{i}", k)
    return reg


# ---------------------------------------------------------------------------
# padded-microbatch equivalence to per-request MoLeSession.deliver
# ---------------------------------------------------------------------------

def test_engine_matches_per_request_deliver(rng):
    reg = _registry(rng)
    eng = MoLeDeliveryEngine(reg, max_rows=8,
                             row_buckets=(1, 2, 4, 8), group_buckets=(1, 2, 4))
    reqs = []
    for i in range(9):  # ragged sizes -> padding in every microbatch
        t = f"t{i % 3}"
        d = rng.standard_normal((1 + i % 4, GEOM.alpha, GEOM.m, GEOM.m)).astype(
            np.float32
        )
        reqs.append((_sub(eng, t, d), t, d))
    done = eng.flush()
    assert sorted(done) == sorted(r for r, _, _ in reqs)
    for rid, t, d in reqs:
        want = np.asarray(reg.session(t).deliver(jnp.asarray(d)))
        got = eng.take(rid)
        assert got.shape == (d.shape[0], GEOM.beta, GEOM.n, GEOM.n)
        np.testing.assert_allclose(got, want, atol=1e-5)


def test_large_request_spans_microbatches(rng):
    reg = _registry(rng, tenants=1)
    eng = MoLeDeliveryEngine(reg, max_rows=4,
                             row_buckets=(1, 2, 4), group_buckets=(1, 2))
    d = rng.standard_normal((19, GEOM.alpha, GEOM.m, GEOM.m)).astype(np.float32)
    feats = _del(eng, "t0", d)
    want = np.asarray(reg.session("t0").deliver(jnp.asarray(d)))
    np.testing.assert_allclose(feats, want, atol=1e-5)
    assert eng.stats.microbatches >= 3  # 19 rows / (2 groups x 4 rows)


def test_engine_delivers_prerolled_rows(rng):
    reg = _registry(rng)
    eng = MoLeDeliveryEngine(reg)
    d = rng.standard_normal((3, GEOM.alpha, GEOM.m, GEOM.m)).astype(np.float32)
    rows = d.reshape(3, -1)
    np.testing.assert_allclose(
        _del(eng, "t1", rows), _del(eng, "t1", d), atol=0
    )


# ---------------------------------------------------------------------------
# multi-tenant isolation
# ---------------------------------------------------------------------------

def test_tenant_rows_use_only_their_own_secrets(rng):
    """Each tenant's engine output equals the plain convolution under *their*
    channel permutation — i.e. morph/unmorph round-tripped through their own
    core, untouched by any co-batched tenant."""
    reg = _registry(rng, tenants=3)
    eng = MoLeDeliveryEngine(reg)
    datas = {
        t: rng.standard_normal((2, GEOM.alpha, GEOM.m, GEOM.m)).astype(np.float32)
        for t in reg.tenant_ids
    }
    rids = {t: _sub(eng, t, d) for t, d in datas.items()}  # one microbatch
    eng.flush()
    for t, d in datas.items():
        feats = eng.take(rids[t])
        want = np.asarray(reg.session(t).deliver(jnp.asarray(d)))
        np.testing.assert_allclose(feats, want, atol=1e-5)


def test_cross_tenant_unmorph_fails(rng):
    """Tenant B's core cannot unmorph tenant A's morphed rows (distinct
    secrets), while A's own core recovers them exactly."""
    reg = _registry(rng, tenants=2)
    a, b = (reg.session(t) for t in reg.tenant_ids)
    x = jnp.asarray(
        rng.standard_normal((4, GEOM.in_features)).astype(np.float32)
    )
    ta = a.provider.morph_rows(x)
    back_a = np.asarray(unmorph(ta, a.provider._core))
    back_b = np.asarray(unmorph(ta, b.provider._core))
    np.testing.assert_allclose(back_a, np.asarray(x), atol=1e-4)
    assert np.max(np.abs(back_b - np.asarray(x))) > 0.1


def test_registry_secrets_are_distinct(rng):
    reg = _registry(rng, tenants=4)
    cores = reg.stacked_cores()
    augs = reg.stacked_aug_matrices()
    assert cores.shape[0] == augs.shape[0] == 4
    for i in range(4):
        for j in range(i + 1, 4):
            assert np.max(np.abs(cores[i] - cores[j])) > 1e-3


def test_flush_on_empty_registry_is_a_noop(rng):
    eng = MoLeDeliveryEngine(SessionRegistry(GEOM, kappa=2))
    assert eng.flush() == {}


def test_default_seeds_are_not_derivable_from_tenant_id(rng):
    """Two registries registering the same tenant id must draw different
    secrets — the default seed comes from OS entropy, not the public id."""
    k = rng.standard_normal((GEOM.alpha, GEOM.beta, GEOM.p, GEOM.p)).astype(
        np.float32
    )
    core_a = SessionRegistry(GEOM, kappa=2).register("t0", k).provider._core
    core_b = SessionRegistry(GEOM, kappa=2).register("t0", k).provider._core
    assert np.max(np.abs(core_a.matrix - core_b.matrix)) > 1e-3


def test_registry_rejects_duplicates_and_unknown_tenants(rng):
    reg = _registry(rng, tenants=1)
    with pytest.raises(ValueError):
        reg.register("t0", np.zeros((2, 4, 3, 3), np.float32))
    eng = MoLeDeliveryEngine(reg)
    with pytest.raises(KeyError):
        _sub(eng, "nobody", np.zeros((1, GEOM.alpha, GEOM.m, GEOM.m)))


def test_late_registration_refreshes_plan(rng):
    reg = _registry(rng, tenants=1)
    eng = MoLeDeliveryEngine(reg)
    d = rng.standard_normal((2, GEOM.alpha, GEOM.m, GEOM.m)).astype(np.float32)
    _del(eng, "t0", d)
    k = rng.standard_normal((GEOM.alpha, GEOM.beta, GEOM.p, GEOM.p)).astype(
        np.float32
    )
    reg.register("late", k)
    got = _del(eng, "late", d)
    want = np.asarray(reg.session("late").deliver(jnp.asarray(d)))
    np.testing.assert_allclose(got, want, atol=1e-5)


# ---------------------------------------------------------------------------
# shape-stable session slots: LRU eviction, host offload, zero-retrace churn
# ---------------------------------------------------------------------------

def test_slotted_registry_lru_eviction_and_offload(rng):
    reg = _registry(rng, tenants=2, capacity=2)
    assert reg.capacity == 2 and reg.resident_tenants == ("t0", "t1")
    k = rng.standard_normal((GEOM.alpha, GEOM.beta, GEOM.p, GEOM.p)).astype(
        np.float32
    )
    reg.register("t2", k)           # full: evicts LRU (t0)
    assert reg.evictions == 1
    assert not reg.is_resident("t0") and reg.is_resident("t2")
    assert "t0" in reg and reg.session("t0") is not None  # host store survives
    # re-activation brings t0 back into a slot (evicting the now-LRU t1)
    slot = reg.slot_for("t0")
    assert reg.is_resident("t0") and 0 <= slot < reg.capacity
    assert not reg.is_resident("t1") and reg.evictions == 2
    # the stacked views stay shape-stable through all of that churn
    assert reg.stacked_cores().shape[0] == 2
    assert reg.stacked_aug_matrices().shape[0] == 2


def test_slotted_registry_auto_capacity_doubles(rng):
    reg = _registry(rng, tenants=5)  # capacity=None: grow, never evict
    assert reg.capacity == 8 and reg.evictions == 0
    assert len(reg.resident_tenants) == 5


def test_slotted_registry_updates_since(rng):
    reg = _registry(rng, tenants=2, capacity=4)
    v0 = reg.version
    assert reg.updates_since(v0) == []
    k = rng.standard_normal((GEOM.alpha, GEOM.beta, GEOM.p, GEOM.p)).astype(
        np.float32
    )
    reg.register("t2", k)
    assert reg.updates_since(v0) == [2]
    reg.evict("t0")
    assert sorted(reg.updates_since(v0)) == [0, 2]
    assert reg.updates_since(reg.version) == []
    assert reg.updates_since(reg.version + 5) is None  # future: rebuild
    # a free slot reads back as zeros (the secret left the device view)
    assert np.all(reg.slot_core(0) == 0) and np.all(reg.slot_aug(0) == 0)


def test_registration_into_free_slot_does_not_retrace(rng):
    """The regression the slot refactor exists for: tenant churn at a fixed
    (bucket, kappa) shape must not retrace _delivery_step."""
    reg = _registry(rng, tenants=1, kappa=2, capacity=4)
    eng = MoLeDeliveryEngine(reg)
    d = rng.standard_normal((3, GEOM.alpha, GEOM.m, GEOM.m)).astype(np.float32)
    _del(eng, "t0", d)            # compiles the (G=1, B=4) bucket
    n0 = delivery_trace_count()
    _del(eng, "t0", d)            # warm bucket: cache hit
    assert delivery_trace_count() == n0
    k = rng.standard_normal((GEOM.alpha, GEOM.beta, GEOM.p, GEOM.p)).astype(
        np.float32
    )
    reg.register("late", k)         # free slot: in-place plan patch
    got = _del(eng, "late", d)
    want = np.asarray(reg.session("late").deliver(jnp.asarray(d)))
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert delivery_trace_count() == n0


def test_eviction_churn_traces_at_most_once_per_bucket(rng):
    """Register/evict/re-activate through a full registry: _delivery_step is
    traced at most once per (bucket, kappa) shape over the whole churn."""
    reg = _registry(rng, tenants=4, kappa=2, capacity=4)
    eng = MoLeDeliveryEngine(reg)
    d = rng.standard_normal((3, GEOM.alpha, GEOM.m, GEOM.m)).astype(np.float32)
    _del(eng, "t0", d)            # one trace for the (G=1, B=4) bucket
    n0 = delivery_trace_count()
    k = lambda: rng.standard_normal(
        (GEOM.alpha, GEOM.beta, GEOM.p, GEOM.p)
    ).astype(np.float32)
    for i in range(4, 10):          # every registration now evicts someone
        reg.register(f"t{i}", k())
        got = _del(eng, f"t{i}", d)
        want = np.asarray(reg.session(f"t{i}").deliver(jnp.asarray(d)))
        np.testing.assert_allclose(got, want, atol=1e-5)
    _del(eng, "t0", d)            # re-activate an evicted tenant
    assert reg.evictions >= 6
    assert delivery_trace_count() == n0  # same bucket throughout: zero traces


def test_non_identity_gather_matches_and_does_not_retrace(rng):
    """The general gather path (T < capacity, out-of-order slots) — the
    ROADMAP's 0.8x-vs-4.9x hazard — must be exactly equivalent to the
    per-request path AND stay retrace-free under churn at a fixed bucket."""
    reg = _registry(rng, tenants=3, capacity=8)   # T < capacity: no fast path
    eng = MoLeDeliveryEngine(reg)
    datas = {
        t: rng.standard_normal((2, GEOM.alpha, GEOM.m, GEOM.m)).astype(
            np.float32
        )
        for t in reg.tenant_ids
    }
    tenants = reg.tenant_ids                      # pinned: churn adds t3 later

    def roundtrip():
        # Reverse registration order -> gidx != arange(G): the general path.
        rids = {t: _sub(eng, t, datas[t]) for t in reversed(tenants)}
        eng.flush()
        for t, rid in rids.items():
            want = np.asarray(reg.session(t).deliver(jnp.asarray(datas[t])))
            np.testing.assert_allclose(eng.take(rid), want, atol=1e-5)

    roundtrip()                                   # compiles the bucket
    n0 = delivery_trace_count()
    roundtrip()                                   # warm: zero new traces
    assert delivery_trace_count() == n0
    k = rng.standard_normal((GEOM.alpha, GEOM.beta, GEOM.p, GEOM.p)).astype(
        np.float32
    )
    reg.register("t3", k)                         # churn into a free slot
    roundtrip()                                   # same bucket, same path
    assert delivery_trace_count() == n0


def test_capacity_growth_rebuilds_plan(rng):
    """Auto-capacity growth is the one churn event allowed to rebuild (and
    so retrace): shapes change, but only O(log T) times."""
    reg = _registry(rng, tenants=1, kappa=2)       # capacity starts at 1
    eng = MoLeDeliveryEngine(reg)
    d = rng.standard_normal((2, GEOM.alpha, GEOM.m, GEOM.m)).astype(np.float32)
    _del(eng, "t0", d)
    k = rng.standard_normal((GEOM.alpha, GEOM.beta, GEOM.p, GEOM.p)).astype(
        np.float32
    )
    reg.register("t1", k)                          # grows 1 -> 2
    assert reg.capacity == 2
    got = _del(eng, "t1", d)
    want = np.asarray(reg.session("t1").deliver(jnp.asarray(d)))
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_registry_rejects_bad_capacity():
    with pytest.raises(ValueError):
        SessionRegistry(GEOM, kappa=2, capacity=0)
    reg = SessionRegistry(GEOM, kappa=2, capacity=2)
    with pytest.raises(KeyError):
        reg.ensure_resident("nobody")


def test_engine_sorts_out_of_order_traffic_into_slot_order(rng):
    """Reverse-order submissions still produce slot-sorted microbatches (the
    grouped kernels' tile-reuse precondition) and exact results."""
    reg = _registry(rng, tenants=4, capacity=8)   # T < capacity
    eng = MoLeDeliveryEngine(reg)
    datas = {
        t: rng.standard_normal((2, GEOM.alpha, GEOM.m, GEOM.m)).astype(
            np.float32
        )
        for t in reg.tenant_ids
    }
    rids = {t: _sub(eng, t, datas[t]) for t in reversed(reg.tenant_ids)}
    work = eng.begin_flush()
    assert len(work.items) == 1
    gidx = work.items[0].mb.group_tenant
    assert np.all(np.diff(gidx) >= 0)             # monotone despite reversal
    eng.execute_flush(work)
    eng.publish_flush(work)
    for t, rid in rids.items():
        want = np.asarray(reg.session(t).deliver(jnp.asarray(datas[t])))
        np.testing.assert_allclose(eng.take(rid), want, atol=1e-5)


def test_flush_rounds_bound_working_set(rng):
    """max_flush_microbatches caps one begin/execute/publish round; flush()
    loops rounds until the backlog drains, completing every request."""
    reg = _registry(rng, tenants=1)
    eng = MoLeDeliveryEngine(
        reg, max_rows=4, row_buckets=(1, 2, 4), group_buckets=(1, 2),
        max_flush_microbatches=1,
    )
    d = rng.standard_normal((19, GEOM.alpha, GEOM.m, GEOM.m)).astype(np.float32)
    rid = _sub(eng, "t0", d)       # 19 rows -> 3+ microbatches
    work = eng.begin_flush()
    assert len(work.items) == 1     # the cap, not the whole backlog
    eng.execute_flush(work)
    assert rid not in eng.publish_flush(work)   # partially delivered
    done = eng.flush()              # loops the remaining rounds
    assert set(done) == {rid}
    np.testing.assert_allclose(
        eng.take(rid), np.asarray(reg.session("t0").deliver(jnp.asarray(d))),
        atol=1e-5,
    )
    assert eng.stats.flushes >= 3


def test_flush_phase_stats_recorded(rng):
    """Every flush records coalesce/device/publish durations; summary()
    renders them for serve.py --stats."""
    reg = _registry(rng, tenants=2)
    eng = MoLeDeliveryEngine(reg)
    d = rng.standard_normal((2, GEOM.alpha, GEOM.m, GEOM.m)).astype(np.float32)
    _del(eng, "t0", d)
    for phase in ("coalesce", "device", "publish"):
        p50 = eng.stats.phase_quantile_ms(phase, 0.5)
        p95 = eng.stats.phase_quantile_ms(phase, 0.95)
        assert p50 == p50 and p95 == p95, phase   # not NaN
        assert 0.0 <= p50 <= p95
    assert "flush" in eng.stats.summary() and "submit wait" in eng.stats.summary()


# ---------------------------------------------------------------------------
# take(): unknown / pending request ids fail with actionable context
# ---------------------------------------------------------------------------

def test_take_unknown_request_id_raises_clear_keyerror(rng):
    reg = _registry(rng, tenants=1)
    eng = MoLeDeliveryEngine(reg)
    with pytest.raises(KeyError, match="unknown request id 123"):
        eng.take(123)


def test_take_unflushed_request_id_raises_pending_context(rng):
    reg = _registry(rng, tenants=1)
    eng = MoLeDeliveryEngine(reg)
    d = rng.standard_normal((3, GEOM.alpha, GEOM.m, GEOM.m)).astype(np.float32)
    rid = _sub(eng, "t0", d)
    with pytest.raises(KeyError, match=r"still pending \(3 rows.*flush"):
        eng.take(rid)
    eng.flush()
    assert eng.take(rid).shape == (3, GEOM.beta, GEOM.n, GEOM.n)
    with pytest.raises(KeyError, match="already taken"):
        eng.take(rid)


# ---------------------------------------------------------------------------
# batched kernel dispatch (CPU path) vs protocol-level morphing
# ---------------------------------------------------------------------------

def test_batched_dispatch_matches_protocol_morph(rng):
    """morph_rows_batched (jnp backend) == per-group morphing.morph."""
    from repro.core.morphing import make_core

    kappa, q, G, B = 2, 16, 3, 5
    cores = [make_core(rng, kappa * q, kappa) for _ in range(G)]
    x = rng.standard_normal((G, B, kappa * q)).astype(np.float32)
    got = morph_rows_batched(
        jnp.asarray(x), jnp.asarray(np.stack([c.matrix for c in cores])),
        kappa, backend="jnp",
    )
    for g in range(G):
        want = np.asarray(morph(jnp.asarray(x[g]), cores[g]))
        np.testing.assert_allclose(np.asarray(got[g]), want, atol=1e-5)


def test_batched_dispatch_backends_agree(rng):
    """jnp reference vs Pallas interpret on a tileable batched shape."""
    G, B, kappa, q = 2, 8, 2, 128
    x = jnp.asarray(rng.standard_normal((G, B, kappa * q)).astype(np.float32))
    cores = jnp.asarray(
        (rng.standard_normal((G, q, q)) / np.sqrt(q)).astype(np.float32)
    )
    got_jnp = morph_rows_batched(x, cores, kappa, backend="jnp")
    got_int = morph_rows_batched(x, cores, kappa, backend="interpret")
    np.testing.assert_allclose(
        np.asarray(got_int), np.asarray(got_jnp), atol=1e-4
    )

    t = jnp.asarray(rng.standard_normal((G, 8, 256)).astype(np.float32))
    c = jnp.asarray(
        (rng.standard_normal((G, 256, 128)) / 16).astype(np.float32)
    )
    np.testing.assert_allclose(
        np.asarray(aug_conv_forward_batched(t, c, backend="interpret")),
        np.asarray(aug_conv_forward_batched(t, c, backend="jnp")),
        atol=1e-4,
    )


def test_batched_ref_fallback_for_nontileable(rng):
    """Off-tile shapes: the jnp backend computes the reference; interpret
    (like compiled Pallas) runs the kernel, never the reference, with a
    10-wide core as one whole-axis block and 3 rows padded to the 8-row
    tile."""
    G, B, kappa, q = 2, 3, 3, 10
    x = jnp.asarray(rng.standard_normal((G, B, kappa * q)).astype(np.float32))
    cores = jnp.asarray(rng.standard_normal((G, q, q)).astype(np.float32))
    want = np.asarray(ref.block_diag_matmul_batched_ref(x, cores, kappa))
    np.testing.assert_allclose(
        np.asarray(morph_rows_batched(x, cores, kappa, backend="jnp")),
        want, atol=1e-5,
    )
    interp = partial(morph_rows_batched, kappa=kappa, backend="interpret")
    assert "pallas_call" in str(jax.make_jaxpr(interp)(x, cores))
    got = interp(x, cores)
    assert got.shape == (G, B, kappa * q)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4)


def test_engine_serves_narrow_core_on_pallas_backends(rng):
    """A kappa > 1 core narrower than the 128-lane tile (2*6*6 / 2 = 36
    features) is served by the kernels on the interpret backend, with the
    same features as per-request delivery."""
    reg = _registry(rng, tenants=2, kappa=2)
    eng = MoLeDeliveryEngine(reg, backend="interpret")
    for i, rows in enumerate((1, 3)):
        d = rng.standard_normal(
            (rows, GEOM.alpha, GEOM.m, GEOM.m)
        ).astype(np.float32)
        np.testing.assert_allclose(
            np.asarray(_del(eng, f"t{i}", d)),
            np.asarray(reg.session(f"t{i}").deliver(jnp.asarray(d))),
            atol=1e-4,
        )


def test_resolve_backend_validates():
    assert resolve_backend("jnp") == "jnp"
    assert resolve_backend("pallas") == "pallas"
    with pytest.raises(ValueError):
        resolve_backend("mosaic")


# ---------------------------------------------------------------------------
# queue coalescing
# ---------------------------------------------------------------------------

def test_queue_buckets_and_padding():
    q = RequestQueue(4, max_rows=8, row_buckets=(1, 2, 4, 8),
                     group_buckets=(1, 2, 4))
    q.submit("a", np.ones((3, 4), np.float32))
    q.submit("b", np.ones((5, 4), np.float32))
    mb = q.coalesce({"a": 0, "b": 1})
    assert mb.x.shape == (2, 8, 4)          # G bucket 2, B bucket 8 (5 -> 8)
    assert mb.n_real_rows == 8
    assert mb.n_padded_rows == 8
    assert list(mb.group_tenant) == [0, 1]
    assert len(q) == 0 and q.coalesce({"a": 0, "b": 1}) is None


def test_queue_same_tenant_requests_share_a_group():
    q = RequestQueue(4, max_rows=8, row_buckets=(1, 2, 4, 8),
                     group_buckets=(1, 2, 4))
    r0 = q.submit("a", np.full((2, 4), 1.0, np.float32))
    r1 = q.submit("a", np.full((3, 4), 2.0, np.float32))
    mb = q.coalesce({"a": 0})
    assert mb.x.shape[0] == 1 and mb.n_real_rows == 5
    # FIFO within the group: request r0's rows precede r1's
    assert np.all(mb.x[0, :2] == 1.0) and np.all(mb.x[0, 2:5] == 2.0)
    by_req = {s.request_id: s for s in mb.slices}
    assert by_req[r0].group_offset == 0 and by_req[r1].group_offset == 2


def test_queue_pending_rows_by_tenant():
    q = RequestQueue(4, max_rows=8, row_buckets=(1, 2, 4, 8),
                     group_buckets=(1, 2, 4))
    q.submit("a", np.ones((3, 4), np.float32))
    q.submit("b", np.ones((5, 4), np.float32))
    q.submit("a", np.ones((2, 4), np.float32))
    assert q.pending_rows_by_tenant() == {"a": 5, "b": 5}
    q.coalesce({"a": 0, "b": 1})
    assert q.pending_rows_by_tenant() == {}


def test_queue_coalesce_orders_groups_by_slot():
    """Groups come out slot-sorted regardless of arrival order, so the
    grouped kernels see monotone indices and the full-table case degenerates
    to gidx == arange."""
    q = RequestQueue(4, max_rows=8, row_buckets=(1, 2, 4, 8),
                     group_buckets=(1, 2, 4))
    q.submit("c", np.full((2, 4), 3.0, np.float32))
    q.submit("a", np.full((2, 4), 1.0, np.float32))
    q.submit("b", np.full((2, 4), 2.0, np.float32))
    mb = q.coalesce({"a": 0, "b": 1, "c": 5})
    assert mb.n_real_groups == 3
    # sorted by slot; the padding group carries its own (clamped) index
    assert list(mb.group_tenant) == [0, 1, 5, 3]
    # each tenant's rows moved with its group
    assert np.all(mb.x[0, :2] == 1.0) and np.all(mb.x[1, :2] == 2.0)
    assert np.all(mb.x[2, :2] == 3.0) and np.all(mb.x[3] == 0.0)


def test_queue_dense_prefix_padding_keeps_arange():
    """Active slots 0..k plus padding degenerate to gidx == arange — the
    layout the jnp backend's in-place fast case keys on."""
    q = RequestQueue(4, max_rows=8, row_buckets=(1, 2, 4, 8),
                     group_buckets=(1, 2, 4))
    for tenant in ("a", "b", "c"):
        q.submit(tenant, np.ones((2, 4), np.float32))
    mb = q.coalesce({"a": 0, "b": 1, "c": 2}, max_groups=4)
    assert mb.n_real_groups == 3
    assert list(mb.group_tenant) == [0, 1, 2, 3]
    # and the clamp keeps padding in range when G buckets past max_groups
    q.submit("a", np.ones((1, 4), np.float32))
    q.submit("b", np.ones((1, 4), np.float32))
    q.submit("c", np.ones((1, 4), np.float32))
    mb = q.coalesce({"a": 0, "b": 1, "c": 2}, max_groups=3)
    assert list(mb.group_tenant) == [0, 1, 2, 2]


def test_queue_overflow_duplicates_stay_adjacent_and_monotone():
    """A tenant overflowing max_rows spans several groups; slot sorting puts
    them next to each other (duplicate indices, still monotone)."""
    q = RequestQueue(4, max_rows=4, row_buckets=(1, 2, 4),
                     group_buckets=(1, 2, 4))
    q.submit("big", np.full((10, 4), 1.0, np.float32))
    q.submit("small", np.full((1, 4), 2.0, np.float32))
    mb = q.coalesce({"big": 2, "small": 0})
    assert mb.n_real_groups == 4           # 3 chunks of "big" + 1 of "small"
    assert list(mb.group_tenant) == [0, 2, 2, 2]
    assert np.all(np.diff(mb.group_tenant) >= 0)
    assert mb.n_real_rows == 11


def test_queue_merges_interleaved_same_tenant_arrivals():
    """a, b, a arrivals: tenant a's two requests share one group (chunk
    building appends to the open chunk), so duplicate slots only remain
    where a tenant truly overflows max_rows."""
    q = RequestQueue(4, max_rows=8, row_buckets=(1, 2, 4, 8),
                     group_buckets=(1, 2, 4))
    r0 = q.submit("a", np.full((2, 4), 1.0, np.float32))
    q.submit("b", np.full((2, 4), 2.0, np.float32))
    r2 = q.submit("a", np.full((3, 4), 3.0, np.float32))
    mb = q.coalesce({"a": 1, "b": 0})
    assert mb.n_real_groups == 2
    assert list(mb.group_tenant) == [0, 1]
    by_req = {s.request_id: s for s in mb.slices}
    # FIFO within the merged group: r0's rows precede r2's
    assert by_req[r0].group == by_req[r2].group == 1
    assert by_req[r0].group_offset == 0 and by_req[r2].group_offset == 2


def test_queue_rejects_bad_shapes():
    q = RequestQueue(4)
    with pytest.raises(ValueError):
        q.submit("a", np.ones((2, 5), np.float32))
    with pytest.raises(ValueError):
        q.submit("a", np.ones((5,), np.float32))


# ---------------------------------------------------------------------------
# sharding rules for the engine microbatch
# ---------------------------------------------------------------------------

def test_delivery_rules_shard_group_axis_only():
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import single_device_mesh
    from repro.sharding import delivery_rules

    rules = delivery_rules(single_device_mesh())
    spec = rules.spec_for(("group", "rows", "features"), (4, 16, 72))
    assert spec == P("data", None, None)
    # stacked secrets replicate
    assert rules.spec_for(("tenant", "core_in", "core_out"), (4, 36, 36)) == P(
        None, None, None
    )
