"""Delivery-engine throughput: batched multi-tenant serving vs per-request.

Sweeps microbatch size x kappa x tenant count on a CIFAR-like first layer and
reports images/sec for (a) the per-request ``MoLeSession.deliver`` baseline —
one unbatched morph + Aug-Conv per request — and (b) the same traffic
coalesced through ``repro.runtime.MoLeDeliveryEngine``.  Also asserts the two
paths agree (the engine is a serving optimization, not an approximation).

A second sweep measures **latency vs throughput** for streaming arrivals:
requests trickle in over time, and per-request completion latency is compared
between (a) the sync engine flushed once after the whole burst has arrived —
early arrivals wait for the stragglers, so p95 grows with the burst size —
and (b) the async front door (``repro.runtime.async_engine``), whose deadline
flusher bounds p95 near ``max_delay_ms`` regardless of burst size.

A third sweep covers the **LM token lane**: batch x sequence-bucket x tenant
count, per-request token morphing (one jitted vocab-permutation gather per
request — the pre-unification ``--mode lm`` path) vs the engine coalescing
all tenants' prompts into length-bucketed token microbatches and morphing
them as one batched multi-tenant gather.  Results are integers, so the
equivalence check is exact.

A **fairness sweep** saturates two tenants — one registered at WFQ weight 2,
one at weight 1 — with identical deep backlogs and runs a fixed number of
bounded flush rounds: the weight-2 tenant must achieve ~2x the goodput
(completed rows) of the weight-1 tenant (gated at >= 1.6x; the allocation is
deterministic scheduler arithmetic, not wall-clock, so the gate also runs in
``--smoke``), with every completed result still exactly equal to per-request
delivery.  A **cross-lane** point repeats the experiment with the weight-2
tenant *splitting* its backlog across the vision and token lanes while the
weight-1 tenant rides vision only: on the one engine-wide virtual clock its
engine-wide service share must still converge to ~2x (gated [1.6, 2.6]x in
full and ``--smoke``) — under the old per-lane clocks each lane granted an
independent 2x and the split tenant inflated to ~4x.

A **prefetch point** drives a strictly periodic tenant on an injected clock
while cache-capacity pressure keeps evicting it: the arrival predictor must
stage the tenant's slot ahead of every tick (``engine.predictive_prefetch``),
so each arrival lands resident — hit rate gated at >= 0.9 in full and
``--smoke`` (deterministic: the clock is injected, not wall time).

A **decode sweep** times end-to-end generation: the per-tenant fallback loop
(fuse Aug params, prefill + greedy-decode one tenant at a time — tenants*gen
single-row dispatches) vs ``repro.runtime.ContinuousDecodeLane`` batching all
tenants into one shared decode step against the registry's stacked AugE
tables and Aug-heads.  Outputs are unmorphed token ids and must be
bit-identical; the full run gates the lane at >= 4x with 16 tenants.  The
``engine/b*`` rows carry no speed gate: they time XLA:CPU, which says
nothing of the chip, where the same grouped kernels run at every batch size.

A fourth sweep measures the **gather cost** the slot-indexed grouped kernels
exist to kill: the same 16-tenant traffic served (a) with capacity == T in
slot order (the old identity-gather fast path), (b) with out-of-order
submission over the same table (the old 0.8x-vs-4.9x hazard — now slot-
sorted back to the identical microbatch, asserted within 1.25x of (a) and
bit-identical), and (c) with T < capacity (a genuinely sparse slot subset —
in-place tile reads on Pallas, a ~2x scan on the jnp CPU reference, gated
far below the old 6-16x gather-copy cliff), plus engine-vs-per-request
agreement.

A **recovery point** times crash recovery: a pending backlog is snapshotted
(``MoLeDeliveryEngine.snapshot``), restored into a freshly built engine, and
flushed — the emitted ``recovery_ms`` is restore + replay-flush.  The point
asserts the crash-safety contract on every run: each snapshotted request is
redeemable exactly once with a bit-identical payload, and the restored flush
adds zero jit retraces (the rebuilt stacked tables keep their shapes, so the
process-global jit cache serves the replay).

CSV rows:
  engine/b{B}_k{kappa}_t{T}/per_request,<us>,<images/s>
  engine/b{B}_k{kappa}_t{T}/engine,<us>,<images/s> speedup=<x>
  engine_fairness/r{rounds}/weight2,<us>,<rows> goodput_ratio=<x>
  engine_fairness/r{rounds}/weight1,<us>,<rows>
  engine_fairness/cross_lane_r{rounds}/weight2_split,<us>,<units> goodput_ratio=<x>
  engine_fairness/cross_lane_r{rounds}/weight1_vision,<us>,<units>
  engine_prefetch/p{period}_n{rounds}/predictive,<us>,hit_rate=<r>
  engine_gather/b{B}_t{T}/identity,<us>,<images/s>
  engine_gather/b{B}_t{T}/partial_table,<us>,<images/s> vs_identity=<x>
  engine_gather/b{B}_t{T}/out_of_order,<us>,<images/s> vs_identity=<x>
  engine_latency/n{N}/sync_flush,<p95 us>,p50=<ms> p95=<ms>
  engine_recovery/b{B}_t{T}/restore_flush,<us>,recovery_ms=<ms>
  engine_latency/n{N}/async_deadline,<p95 us>,p50=<ms> p95=<ms> SLO=<ms>
  engine_lm/b{B}_s{L}_t{T}/per_request,<us>,<prompts/s>
  engine_lm/b{B}_s{L}_t{T}/engine,<us>,<prompts/s> speedup=<x>
  engine_decode/t{T}_g{G}/per_tenant,<us>,<tok/s>
  engine_decode/t{T}_g{G}/lane,<us>,<tok/s> speedup=<x> bit_identical

``--json PATH`` additionally writes every row to a machine-readable file
(the committed ``BENCH_delivery.json`` trajectory point); ``--smoke`` runs a
tiny-shape subset as the CI per-PR job, keeping the non-identity gather path
exercised on every change.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from .common import emit, write_json

GEOM = dict(alpha=3, beta=16, m=16, p=3)   # CIFAR-ish first conv layer


def _req(tenant: str, payload, **kw):
    from repro.runtime import DeliveryRequest

    return DeliveryRequest(tenant, payload, **kw)


def _build(tenants: int, kappa: int, seed: int = 0):
    from repro.core import ConvGeometry, SessionRegistry
    from repro.runtime import MoLeDeliveryEngine

    rng = np.random.default_rng(seed)
    geom = ConvGeometry(**GEOM)
    # Capacity == tenant count: steady-state microbatches carry no padding
    # groups and slot-sort to gidx == arange.
    registry = SessionRegistry(geom, kappa=kappa, capacity=tenants)
    fan_in = geom.alpha * geom.p * geom.p
    for i in range(tenants):
        k = rng.standard_normal(
            (geom.alpha, geom.beta, geom.p, geom.p)
        ).astype(np.float32) / np.sqrt(fan_in)
        registry.register(f"tenant-{i}", k)
    engine = MoLeDeliveryEngine(registry)
    return geom, registry, engine, rng


def _sweep_point(batch: int, kappa: int, tenants: int) -> None:
    geom, registry, engine, rng = _build(tenants, kappa)
    requests = [
        (f"tenant-{i % tenants}",
         rng.standard_normal((1, geom.alpha, geom.m, geom.m)).astype(np.float32))
        for i in range(batch)
    ]

    # Warmup replays the full request pattern so the timed passes hit the
    # exact (G, B) buckets already compiled.
    for t, d in requests:
        engine.submit(_req(t, d))
    engine.flush()
    for t, d in requests:
        jax.block_until_ready(registry.session(t).deliver(jnp.asarray(d)))

    iters = 5
    t0 = time.perf_counter()
    for _ in range(iters):
        base = [
            np.asarray(registry.session(t).deliver(jnp.asarray(d)))
            for t, d in requests
        ]
    dt_req = (time.perf_counter() - t0) / iters

    t0 = time.perf_counter()
    for _ in range(iters):
        rids = [engine.submit(_req(t, d)) for t, d in requests]
        engine.flush()
        feats = [engine.take(r) for r in rids]
    dt_eng = (time.perf_counter() - t0) / iters

    err = max(float(np.max(np.abs(f - b))) for f, b in zip(feats, base))
    assert err < 1e-5, f"engine/per-request mismatch: {err}"

    speedup = dt_req / dt_eng
    tag = f"engine/b{batch}_k{kappa}_t{tenants}"
    emit(f"{tag}/per_request", dt_req * 1e6, f"{batch / dt_req:.1f} images/s")
    emit(
        f"{tag}/engine", dt_eng * 1e6,
        f"{batch / dt_eng:.1f} images/s speedup={speedup:.2f}x "
        f"err={err:.1e}",
    )


def _time_engine(engine, requests, iters: int = 5) -> tuple[float, list]:
    """Seconds per replay of ``requests`` through submit/flush/take."""
    t0 = time.perf_counter()
    for _ in range(iters):
        rids = [engine.submit(_req(t, d)) for t, d in requests]
        engine.flush()
        feats = [engine.take(r) for r in rids]
    return (time.perf_counter() - t0) / iters, feats


def _gather_sweep_point(
    batch: int, tenants: int, kappa: int = 1,
    max_ratio: float | None = 1.25, sparse_max_ratio: float | None = 3.0,
    iters: int = 5,
) -> None:
    """Identity vs non-identity slot-index cost (the ROADMAP 0.8x-vs-4.9x
    hazard).  One traffic pattern, three slot layouts:

      identity:      capacity == T, slot-order round-robin -> gidx == arange
      out_of_order:  same registry, submission order shuffled — the old
                     engine saw a permuted gidx and fell off the fast path
                     (the 0.8x case); slot-sorted coalescing restores the
                     very same arange microbatch, so this must now cost the
                     same as identity (``max_ratio``, default 1.25x) and be
                     bit-identical to the sorted run.
      partial_table: 2T slots registered, traffic to every other one ->
                     gidx == [0, 2, 4, ...]: genuinely sparse.  The Pallas
                     grouped kernels read each tile in place for any layout
                     (no gather, ~1.0x by construction); the jnp reference
                     has no gather-free batched GEMM available in XLA:CPU,
                     so its scan of dynamic slices pays ~2x vs the in-place
                     einsum — gated at ``sparse_max_ratio`` (down from the
                     6-16x gather-copy cliff this sweep used to show).
    """
    geom, registry, engine, rng = _build(tenants, kappa)
    requests = [
        (f"tenant-{i % tenants}",
         rng.standard_normal((1, geom.alpha, geom.m, geom.m)).astype(np.float32))
        for i in range(batch)
    ]

    def _prep(engine_, reqs):  # warm the exact (G, B) buckets, then time
        for t, d in reqs:
            engine_.submit(_req(t, d))
        for rid in engine_.flush():
            engine_.take(rid)  # release the warm-up result buffers
        return _time_engine(engine_, reqs, iters)

    dt_id, feats_id = _prep(engine, requests)

    # Shuffled submission over the same full table: the queue sorts it back
    # into the identical slot-order microbatch — asserted bit-identical.
    order = np.random.default_rng(7).permutation(len(requests))
    dt_oo, feats_oo = _prep(engine, [requests[i] for i in order])
    for i, j in enumerate(order):
        assert np.array_equal(feats_oo[i], feats_id[j]), "sort changed math"

    # T < capacity: register 2T tenants, steer the same traffic to every
    # other slot — a sparse, sorted, non-arange index vector.
    geom2, registry2, engine2, _ = _build(2 * tenants, kappa)
    sparse = [(f"tenant-{2 * int(t.split('-')[1])}", d) for t, d in requests]
    dt_sp, feats_sp = _prep(engine2, sparse)

    err_sp = max(
        float(np.max(np.abs(f - registry2.session(t).deliver(jnp.asarray(d)))))
        for f, (t, d) in zip(feats_sp, sparse)
    )
    assert err_sp < 1e-5, f"engine/per-request mismatch: {err_sp}"

    tag = f"engine_gather/b{batch}_t{tenants}"
    emit(f"{tag}/identity", dt_id * 1e6, f"{batch / dt_id:.1f} images/s")
    for case, dt, limit, exact in (
        ("out_of_order", dt_oo, max_ratio, "bit_identical_to_identity"),
        ("partial_table", dt_sp, sparse_max_ratio, f"err={err_sp:.1e}"),
    ):
        ratio = dt / dt_id
        emit(
            f"{tag}/{case}", dt * 1e6,
            f"{batch / dt:.1f} images/s vs_identity={ratio:.2f}x {exact}",
        )
        assert limit is None or ratio < limit, (
            f"{case} gather path {ratio:.2f}x slower than identity "
            f"(limit {limit}x)"
        )


LM_VOCAB, LM_DMODEL = 1024, 64


def _build_lm(tenants: int, seed: int = 0):
    from repro.core.lm import LMSessionRegistry
    from repro.runtime import MoLeDeliveryEngine

    rng = np.random.default_rng(seed)
    # Capacity == tenant count keeps steady-state token microbatches free of
    # padding groups, mirroring the vision sweep.
    registry = LMSessionRegistry(LM_VOCAB, LM_DMODEL, capacity=tenants)
    for i in range(tenants):
        registry.register(
            f"tenant-{i}",
            rng.standard_normal((LM_VOCAB, LM_DMODEL)).astype(np.float32),
            seed=i,
        )
    engine = MoLeDeliveryEngine(lm_registry=registry)
    return registry, engine, rng


def _token_sweep_point(batch: int, seq: int, tenants: int) -> None:
    """Batched multi-tenant token morphing vs one gather per request."""
    registry, engine, rng = _build_lm(tenants)
    requests = [
        (f"tenant-{i % tenants}",
         rng.integers(0, LM_VOCAB, (1, seq)).astype(np.int32))
        for i in range(batch)
    ]

    # Per-request baseline: the pre-unification --mode lm path — one
    # ``TokenMorpher.morph_tokens`` call per request (mirrors the vision
    # sweep's per-request ``MoLeSession.deliver`` baseline).
    # Warmup replays the full pattern so the timed passes hit compiled
    # buckets on both paths.
    for t, d in requests:
        engine.submit(_req(t, d, lane="tokens"))
    engine.flush()
    for t, d in requests:
        jax.block_until_ready(
            registry.session(t).morph_tokens(jnp.asarray(d))
        )

    iters = 5
    t0 = time.perf_counter()
    for _ in range(iters):
        base = [
            np.asarray(registry.session(t).morph_tokens(jnp.asarray(d)))
            for t, d in requests
        ]
    dt_req = (time.perf_counter() - t0) / iters

    t0 = time.perf_counter()
    for _ in range(iters):
        rids = [engine.submit(_req(t, d, lane="tokens")) for t, d in requests]
        engine.flush()
        morphed = [engine.take(r) for r in rids]
    dt_eng = (time.perf_counter() - t0) / iters

    for m, b in zip(morphed, base):
        assert np.array_equal(m, b), "engine/per-request token morph mismatch"

    tag = f"engine_lm/b{batch}_s{seq}_t{tenants}"
    emit(f"{tag}/per_request", dt_req * 1e6, f"{batch / dt_req:.1f} prompts/s")
    emit(
        f"{tag}/engine", dt_eng * 1e6,
        f"{batch / dt_eng:.1f} prompts/s speedup={dt_req / dt_eng:.2f}x "
        f"err=0.0e+00",
    )


def _fairness_sweep_point(
    requests_per_tenant: int = 64, rows_per_request: int = 8,
    rounds: int = 8, min_ratio: float = 1.6, max_ratio: float = 2.6,
) -> None:
    """Saturated 2-tenant WFQ fairness: a weight-2 tenant must achieve ~2x
    the goodput (completed rows) of a weight-1 tenant when both hold deep
    identical backlogs and only ``rounds`` bounded flush rounds run.

    The allocation is deterministic scheduler arithmetic (virtual-time
    bookkeeping, not wall-clock), so the ratio gate holds on any machine —
    including the CI ``--smoke`` job; only the emitted us/round is timing.
    """
    from repro.core import ConvGeometry, SessionRegistry
    from repro.runtime import MoLeDeliveryEngine

    geom = ConvGeometry(**GEOM)
    rng = np.random.default_rng(3)
    registry = SessionRegistry(geom, kappa=1, capacity=2)
    fan_in = geom.alpha * geom.p * geom.p
    for name, w in (("heavy", 2.0), ("light", 1.0)):
        k = rng.standard_normal(
            (geom.alpha, geom.beta, geom.p, geom.p)
        ).astype(np.float32) / np.sqrt(fan_in)
        registry.register(name, k, weight=w)
    engine = MoLeDeliveryEngine(
        registry, max_rows=rows_per_request,
        row_buckets=tuple(sorted({1, 2, 4, rows_per_request})),
        group_buckets=(1, 2), max_flush_microbatches=4,
    )

    datas: dict[int, tuple[str, np.ndarray]] = {}
    for _ in range(requests_per_tenant):
        for t in ("heavy", "light"):   # interleaved identical backlogs
            d = rng.standard_normal(
                (rows_per_request, geom.alpha, geom.m, geom.m)
            ).astype(np.float32)
            datas[engine.submit(_req(t, d))] = (t, d)

    # Bounded rounds against a saturating backlog: WFQ decides whose rows
    # fill the capped microbatch budget.
    served = {"heavy": 0, "light": 0}
    done_rids: list[int] = []
    t0 = time.perf_counter()
    for _ in range(rounds):
        work = engine.begin_flush()
        assert work is not None, "backlog drained: not saturated, grow it"
        engine.execute_flush(work)
        for rid in engine.publish_flush(work):
            t, d = datas[rid]
            served[t] += d.shape[0]
            done_rids.append(rid)
    dt = (time.perf_counter() - t0) / rounds

    # Completed results are still exactly the per-request delivery.
    err = max(
        float(np.max(np.abs(
            engine.take(rid)
            - np.asarray(
                registry.session(datas[rid][0]).deliver(
                    jnp.asarray(datas[rid][1])
                )
            )
        )))
        for rid in done_rids[:8]
    )
    assert err < 1e-5, f"fairness sweep equivalence broke: {err}"

    ratio = served["heavy"] / max(served["light"], 1)
    tag = f"engine_fairness/r{rounds}"
    emit(
        f"{tag}/weight2", dt * 1e6,
        f"{served['heavy']} rows goodput_ratio={ratio:.2f}x err={err:.1e}",
    )
    emit(f"{tag}/weight1", dt * 1e6, f"{served['light']} rows")
    assert min_ratio <= ratio <= max_ratio, (
        f"weight-2 tenant got {ratio:.2f}x the weight-1 goodput "
        f"(want [{min_ratio}, {max_ratio}]x)"
    )


def _cross_lane_fairness_point(
    requests_per_tenant: int = 12, rows_per_request: int = 8,
    rounds: int = 8, min_ratio: float = 1.6, max_ratio: float = 2.6,
) -> None:
    """The cross-lane weight-inflation regression, as a gated trajectory
    point: "heavy" (weight 2) splits a saturating backlog across the vision
    AND token lanes, "light" (weight 1) rides vision only.  On the shared
    engine-wide clock heavy's total service over ``rounds`` bounded flush
    rounds must still be ~2x light's (per-lane clocks used to give each of
    heavy's lanes a full 2x share => ~4x engine-wide).  Deterministic
    scheduler arithmetic — the gate runs in ``--smoke`` too.
    """
    from repro.core import ConvGeometry, SessionRegistry
    from repro.core.lm import LMSessionRegistry
    from repro.runtime import MoLeDeliveryEngine

    geom = ConvGeometry(**GEOM)
    rng = np.random.default_rng(11)
    registry = SessionRegistry(geom, kappa=1, capacity=2)
    fan_in = geom.alpha * geom.p * geom.p
    for name, w in (("heavy", 2.0), ("light", 1.0)):
        k = rng.standard_normal(
            (geom.alpha, geom.beta, geom.p, geom.p)
        ).astype(np.float32) / np.sqrt(fan_in)
        registry.register(name, k, weight=w)
    lm_registry = LMSessionRegistry(LM_VOCAB, LM_DMODEL, capacity=1)
    lm_registry.register(
        "heavy",
        rng.standard_normal((LM_VOCAB, LM_DMODEL)).astype(np.float32),
        seed=0,
    )
    engine = MoLeDeliveryEngine(
        registry, lm_registry=lm_registry, max_rows=rows_per_request,
        row_buckets=tuple(sorted({1, 2, 4, rows_per_request})),
        group_buckets=(1, 2), seq_buckets=(rows_per_request,),
        max_flush_microbatches=2,
    )

    for _ in range(requests_per_tenant):
        engine.submit(_req("heavy", rng.standard_normal(
            (rows_per_request, geom.alpha, geom.m, geom.m)
        ).astype(np.float32)))
        engine.submit(_req(
            "heavy",
            rng.integers(
                0, LM_VOCAB, (rows_per_request, rows_per_request)
            ).astype(np.int32),
            lane="tokens",
        ))
        for _ in range(2):   # light matches heavy's total demand, on vision
            engine.submit(_req("light", rng.standard_normal(
                (rows_per_request, geom.alpha, geom.m, geom.m)
            ).astype(np.float32)))

    t0 = time.perf_counter()
    for _ in range(rounds):
        work = engine.begin_flush()
        assert work is not None, "backlog drained: not saturated, grow it"
        engine.execute_flush(work)
        engine.publish_flush(work)
    dt = (time.perf_counter() - t0) / rounds

    served = engine.scheduler.service_by_tenant
    ratio = served["heavy"] / max(served["light"], 1)
    tag = f"engine_fairness/cross_lane_r{rounds}"
    emit(
        f"{tag}/weight2_split", dt * 1e6,
        f"{served['heavy']} units goodput_ratio={ratio:.2f}x",
    )
    emit(f"{tag}/weight1_vision", dt * 1e6, f"{served['light']} units")
    assert min_ratio <= ratio <= max_ratio, (
        f"weight-2 tenant splitting across lanes got {ratio:.2f}x the "
        f"weight-1 goodput (want [{min_ratio}, {max_ratio}]x: per-lane "
        f"clock inflation is back)"
    )


def _prefetch_point(
    rounds: int = 8, period_s: float = 10.0, min_hit_rate: float = 0.9,
) -> None:
    """Predictive prefetch on an injected clock: a strictly periodic tenant
    keeps losing its slot to capacity pressure; the arrival predictor must
    re-stage it ahead of every tick so each arrival lands resident.  The
    emitted us is the ``predictive_prefetch`` call itself (predictor scan +
    slot staging); the hit-rate gate is deterministic and runs in
    ``--smoke``."""
    from repro.core import ConvGeometry, SessionRegistry
    from repro.runtime import MoLeDeliveryEngine

    geom = ConvGeometry(**GEOM)
    rng = np.random.default_rng(13)
    registry = SessionRegistry(geom, kappa=1, capacity=2)
    fan_in = geom.alpha * geom.p * geom.p
    for name in ("hot", "filler-a", "filler-b"):
        k = rng.standard_normal(
            (geom.alpha, geom.beta, geom.p, geom.p)
        ).astype(np.float32) / np.sqrt(fan_in)
        registry.register(name, k)
    now = [0.0]
    engine = MoLeDeliveryEngine(
        registry, max_rows=8, row_buckets=(1, 2, 4, 8), group_buckets=(1, 2),
        clock=lambda: now[0],
    )
    data = rng.standard_normal(
        (2, geom.alpha, geom.m, geom.m)
    ).astype(np.float32)

    # Learn the period: 4 ticks while resident, then the eviction cycle.
    for tick in range(4):
        now[0] = period_s * tick
        engine.submit(_req("hot", data))
        engine.flush()

    spent = 0.0
    for r in range(rounds):
        engine.prefetch(["filler-a", "filler-b"])   # capacity 2: evicts hot
        assert not registry.is_resident("hot")
        next_tick = period_s * (4 + r)
        now[0] = next_tick - 2.0
        t0 = time.perf_counter()
        staged = engine.predictive_prefetch(horizon_ms=5_000.0)
        spent += time.perf_counter() - t0
        assert staged == ["hot"], f"predictor failed to stage: {staged}"
        now[0] = next_tick
        engine.submit(_req("hot", data))
        engine.flush()
    hits, misses = engine.stats.prefetch_hits, engine.stats.prefetch_misses
    rate = hits / max(hits + misses, 1)
    emit(
        f"engine_prefetch/p{period_s:g}_n{rounds}/predictive",
        spent / rounds * 1e6,
        f"hit_rate={rate:.2f} hits={hits} misses={misses}",
    )
    assert rate >= min_hit_rate, (
        f"predictive prefetch hit rate {rate:.2f} < {min_hit_rate} "
        f"(hits={hits} misses={misses})"
    )


def _latency_point(
    n_requests: int, max_delay_ms: float = 2.0, arrival_ms: float = 0.5
) -> None:
    """Streaming arrivals: sync flush-after-burst vs async deadline flusher."""
    from repro.runtime import AsyncDeliveryEngine, EngineStats

    tenants = 4
    geom, registry, engine, rng = _build(tenants, kappa=1, seed=1)
    datas = [
        (f"tenant-{i % tenants}",
         rng.standard_normal((1, geom.alpha, geom.m, geom.m)).astype(np.float32))
        for i in range(n_requests)
    ]

    # Warm every bucket the two runs may hit (compile outside the timers):
    # the deadline flusher lands on small (G, B) buckets that depend on how
    # many requests arrive per SLO window — anywhere from one request to the
    # whole open-loop backlog if a flush runs long — so sweep group-count x
    # rows-per-tenant up to n_requests//tenants, then the sync burst bucket,
    # then replay the async arrival pattern once (the _delivery_step jit
    # cache is process-global).
    per_tenant_lattice = sorted(
        {1, 2, 3, 4, 8, 16, 32, 64} & set(range(1, n_requests // tenants + 1))
    )
    for n_tenants in (1, 2, 4):
        for per_tenant in per_tenant_lattice:
            rids = [
                engine.submit(_req(t, d))
                for t, d in datas[: n_tenants * per_tenant]
            ]
            engine.flush()
            for r in rids:
                engine.take(r)
    rids = [engine.submit(_req(t, d)) for t, d in datas]
    engine.flush()
    for r in rids:
        engine.take(r)
    warm = AsyncDeliveryEngine(engine, max_delay_ms=max_delay_ms)
    futs = []
    for t, d in datas:
        time.sleep(arrival_ms / 1e3)
        futs.append(warm.submit(_req(t, d)))
    for f in futs:
        f.result(timeout=120)
    warm.close()

    # (a) sync: requests arrive over time, one flush once all have arrived.
    # Latencies go through a fresh EngineStats so both rows use the same
    # quantile estimator.
    sync_stats = EngineStats()
    submit_at: dict[int, float] = {}
    rids = []
    for t, d in datas:
        time.sleep(arrival_ms / 1e3)
        rid = engine.submit(_req(t, d))
        submit_at[rid] = time.perf_counter()
        rids.append(rid)
    engine.flush()
    t_done = time.perf_counter()
    for r in rids:
        engine.take(r)
        sync_stats.record_latency_ms((t_done - submit_at[r]) * 1e3)

    # (b) async: same arrival pattern through the deadline flusher.  Fresh
    # stats so the emitted p50/p95/flushes describe this run only.
    engine.stats = EngineStats()
    front = AsyncDeliveryEngine(engine, max_delay_ms=max_delay_ms)
    futures = []
    for t, d in datas:
        time.sleep(arrival_ms / 1e3)
        futures.append(front.submit(_req(t, d)))
    for f in futures:
        f.result(timeout=120)
    stats = engine.stats
    front.close()

    tag = f"engine_latency/n{n_requests}"
    emit(
        f"{tag}/sync_flush", sync_stats.p95_ms * 1e3,
        f"p50={sync_stats.p50_ms:.2f}ms p95={sync_stats.p95_ms:.2f}ms",
    )
    emit(
        f"{tag}/async_deadline", stats.p95_ms * 1e3,
        f"p50={stats.p50_ms:.2f}ms p95={stats.p95_ms:.2f}ms "
        f"SLO={max_delay_ms}ms flushes={stats.flushes}",
    )


def _decode_sweep_point(
    tenants: int = 16, gen: int = 16, prompt_len: int = 16,
    min_speedup: float | None = 4.0, iters: int = 3,
) -> None:
    """Continuous-batched cross-tenant decode vs the per-tenant loop.

    One generation request per tenant on a smoke LM.  Baseline is the
    pre-lane serving path (``launch.serve``'s fallback branch): fuse each
    tenant's Aug params, then prefill + greedy-decode that tenant alone —
    ``tenants * gen`` single-row device dispatches.  The lane runs the same
    traffic as one ``ContinuousDecodeLane``: per-row prefills, then ``gen``
    shared batched decode steps against the registry's stacked AugE tables
    and Aug-heads.  Both sides unmorph to the provider view and must be
    bit-identical (conjugation by the vocab permutation moves bits).
    """
    import dataclasses

    from repro.configs import get_smoke_config
    from repro.core.deploy import fuse_lm_params
    from repro.core.lm import LMSessionRegistry
    from repro.launch.steps import make_decode_step, make_prefill_step
    from repro.models.api import Model
    from repro.models.base import MoLeCfg
    from repro.runtime import ContinuousDecodeLane

    cfg = dataclasses.replace(
        get_smoke_config("deepseek_7b"),   # untied head, no frontend, fp32
        mole=MoLeCfg(enabled=True, mode="token"),
    )
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    embed = np.asarray(params["embed"], np.float32)
    head = np.asarray(params["head"], np.float32)
    registry = LMSessionRegistry(cfg.vocab, cfg.d_model, capacity=tenants)
    for i in range(tenants):
        registry.register(f"lm-{i}", embed, seed=cfg.mole.seed + i, head=head)

    rng = np.random.default_rng(5)
    prompts = [
        rng.integers(0, cfg.vocab, prompt_len).astype(np.int32)
        for _ in range(tenants)
    ]
    max_len = prompt_len + gen + 1

    prefill = jax.jit(make_prefill_step(model))
    decode = jax.jit(make_decode_step(model))

    def per_tenant_loop() -> list[np.ndarray]:
        outs = []
        for i in range(tenants):
            sess = registry.session(f"lm-{i}")
            dev = fuse_lm_params(params, cfg, token_morpher=sess.morpher)
            served = np.asarray(sess.morpher.perm)[prompts[i]][None, :]
            caches = model.init_cache(1, max_len)
            logits, caches = prefill(
                dev, {"tokens": jnp.asarray(served, jnp.int32)}, caches
            )
            tok = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)[:, None]
            toks = [tok]
            for s in range(gen - 1):
                logits, caches = decode(
                    dev, tok, jnp.asarray(prompt_len + s, jnp.int32), caches
                )
                tok = jnp.argmax(logits[:, 0], axis=-1).astype(
                    jnp.int32
                )[:, None]
                toks.append(tok)
            served_out = np.concatenate(
                [np.asarray(t) for t in toks], axis=1
            )[0]
            outs.append(
                np.asarray(sess.morpher.inv_perm)[served_out].astype(np.int32)
            )
        return outs

    # One lane reused across replays: rows all retire at the end of run(),
    # so each replay is a fresh join/decode/leave cycle on the same compiled
    # step (building a new lane per replay would re-jit the closures).
    lane = ContinuousDecodeLane(
        model, params, registry, rows=tenants, max_len=max_len
    )

    def lane_run() -> list[np.ndarray]:
        sids = [
            lane.submit(f"lm-{i}", prompts[i], gen) for i in range(tenants)
        ]
        lane.run()
        return [lane.take(s) for s in sids]

    base = per_tenant_loop()   # warm + reference
    got = lane_run()           # warm (compiles the batched step once)
    for b, g in zip(base, got):
        np.testing.assert_array_equal(b, g)

    t0 = time.perf_counter()
    for _ in range(iters):
        per_tenant_loop()
    dt_loop = (time.perf_counter() - t0) / iters
    t0 = time.perf_counter()
    for _ in range(iters):
        lane_run()
    dt_lane = (time.perf_counter() - t0) / iters

    toks = tenants * gen
    speedup = dt_loop / dt_lane
    tag = f"engine_decode/t{tenants}_g{gen}"
    emit(f"{tag}/per_tenant", dt_loop * 1e6, f"{toks / dt_loop:.1f} tok/s")
    emit(
        f"{tag}/lane", dt_lane * 1e6,
        f"{toks / dt_lane:.1f} tok/s speedup={speedup:.2f}x bit_identical",
    )
    if min_speedup is not None:
        assert speedup >= min_speedup, (
            f"{tag}: decode lane {speedup:.2f}x < {min_speedup:.2f}x "
            f"vs the per-tenant loop"
        )


def _recovery_point(
    backlog: int = 32, tenants: int = 4, iters: int = 5
) -> None:
    """Crash-recovery latency: snapshot a pending backlog, restore it into a
    freshly built engine, flush the replay.  ``recovery_ms`` is the restore +
    replay-flush wall time; the exactly-once and zero-retrace contracts are
    asserted on every iteration (so the committed trajectory point doubles
    as a correctness gate)."""
    from repro.runtime import delivery_trace_count

    geom, registry, engine, rng = _build(tenants, kappa=1, seed=2)
    requests = [
        (f"tenant-{i % tenants}",
         rng.standard_normal((1, geom.alpha, geom.m, geom.m)).astype(np.float32))
        for i in range(backlog)
    ]
    # Warm the exact (G, B) buckets the replayed flush will hit, then leave
    # the same pattern pending and snapshot it.
    warm = [engine.submit(_req(t, d)) for t, d in requests]
    engine.flush()
    for rid in warm:
        engine.take(rid)
    rids = [engine.submit(_req(t, d)) for t, d in requests]
    snap = engine.snapshot()
    # Reference = the uninterrupted engine finishing the same backlog: the
    # restored replay must be bit-identical to the run that never crashed.
    engine.flush()
    want = {r: engine.take(r) for r in rids}

    total = 0.0
    for _ in range(iters):
        # A fresh engine over a fresh (differently seeded) registry shell:
        # restore() overwrites its secrets with the snapshot's.
        _, _, engine2, _ = _build(tenants, kappa=1, seed=3)
        n0 = delivery_trace_count()
        t0 = time.perf_counter()
        replayed = engine2.restore(snap)
        engine2.flush()
        total += time.perf_counter() - t0
        assert delivery_trace_count() == n0, "restore retraced the step"
        assert replayed == rids, "lost/duplicated rids across restore"
        for r in rids:
            assert np.array_equal(engine2.take(r), want[r])
    dt = total / iters
    emit(
        f"engine_recovery/b{backlog}_t{tenants}/restore_flush", dt * 1e6,
        f"{backlog / dt:.1f} images/s recovery_ms={dt * 1e3:.2f} "
        f"exactly_once zero_retrace",
    )


def _served_chaos_point(
    chaos_requests: int = 24, overload_requests: int = 24
) -> None:
    """End-to-end front-door trajectory point: the real ``--mode serve``
    front door, built from its own flags and served in this process, with
    network chaos armed on both sides plus one injected flusher crash, then
    driven with the retrying client fleet.  One process holds the device
    for the server and the fleet alike.

    Two runs share one server (amortizing its warmup):

    * ``served_chaos`` — conn drops, truncated frames, stalled reads, and a
      one-shot device failure mid-run; the exactly-once guarantee
      (:meth:`FleetReport.assert_exactly_once`) is the correctness gate and
      the emitted latency is the ok-p50 as the client observed it.
    * ``served_overload`` — a single burst far above ``--max-pending-rows``;
      the gate is that the server sheds with typed OVERLOADED rejections
      while the p99 of *accepted* requests stays bounded (no collapse).
    """
    import asyncio

    from repro.launch.client import FleetConfig, run_fleet
    from repro.launch.serve import parse_args
    from repro.launch.server import build_front, close_front, make_server
    from repro.runtime.resilience import FailureInjector

    args = parse_args([
        "--mode", "serve",
        "--channels", "2", "--out-channels", "4", "--image-size", "6",
        "--kappa", "2", "--tenants", "3", "--warm-batch", "4",
        "--max-pending-rows", "48", "--max-delay-ms", "5",
        "--chaos", "--chaos-rate", "0.1", "--chaos-seed", "7",
        "--inject-failure", "device",
    ])
    front = build_front(args)

    async def fleets(server) -> None:
        port = server.port
        chaos = FailureInjector(
            network_phases={"write", "read", "stall"},
            network_rate=0.1, stall_ms=50.0, seed=11,
        )
        t0 = time.perf_counter()
        rep = await run_fleet(FleetConfig(
            port=port, requests=chaos_requests, clients=4, tenants=3,
            batch=2, channels=2, image_size=6, trace="uniform:300",
            timeout_ms=30000.0, attempt_timeout_ms=1500.0, max_attempts=8,
            seed=3, fleet_id="bench-chaos", chaos=chaos,
        ))
        dt = time.perf_counter() - t0
        rep.assert_exactly_once()
        ok = rep.counts().get("ok", 0)
        assert ok >= chaos_requests // 2, (
            f"chaos fleet: only {ok}/{chaos_requests} ok — the retry "
            f"protocol is not riding out the injected faults"
        )
        emit(
            f"served_chaos/n{chaos_requests}_r0.1/fleet",
            rep.quantile_ms(0.50) * 1e3,
            f"{chaos_requests / dt:.1f} req/s ok={ok}/{chaos_requests} "
            f"hedges={rep.hedges} drops={rep.conn_drops} exactly_once",
        )

        rep2 = await run_fleet(FleetConfig(
            port=port, requests=overload_requests, clients=8, tenants=3,
            batch=4, channels=2, image_size=6,
            trace=f"burst:{overload_requests}@1",
            # The server still has --chaos armed: conn drops need retry
            # headroom and lost responses need a quick hedge trigger, or
            # accepted-request latency is dominated by the wait.  A shed
            # still resolves on the first OVERLOADED frame regardless.
            timeout_ms=30000.0, attempt_timeout_ms=2000.0, max_attempts=4,
            seed=5, fleet_id="bench-over",
        ))
        rep2.assert_exactly_once()
        shed = rep2.counts().get("rejected:OVERLOADED", 0)
        ok2 = rep2.counts().get("ok", 0)
        assert shed > 0, "overload burst produced no typed OVERLOADED sheds"
        p99 = rep2.quantile_ms(0.99)
        assert ok2 == 0 or p99 < 15000.0, (
            f"accepted-request p99 {p99:.0f}ms under overload — shedding "
            f"is not bounding the queue"
        )
        emit(
            f"served_overload/n{overload_requests}_cap48/fleet",
            (p99 if ok2 else 0.0) * 1e3,
            f"ok={ok2} shed={shed} typed_rejections p99_bounded",
        )

    async def serve() -> int:
        server = make_server(front, args)
        await server.start()
        try:
            await fleets(server)
        finally:
            lost = await server.drain_and_stop(
                timeout=args.drain_timeout_ms / 1e3
            )
        return lost

    rc = close_front(front, asyncio.run(serve()))
    assert rc == 0, f"server exited {rc} after the drain (lost rids?)"


def run() -> None:
    for batch in (8, 64):
        for kappa in (1, 4):
            for tenants in (1, 4, 16):
                _sweep_point(batch, kappa, tenants)
    _fairness_sweep_point()
    _cross_lane_fairness_point()
    _prefetch_point()
    _gather_sweep_point(batch=64, tenants=16)
    for batch in (8, 64):
        for seq in (16, 128):
            for tenants in (1, 4, 16):
                _token_sweep_point(batch, seq, tenants)
    _decode_sweep_point(tenants=16, gen=16)
    _recovery_point(backlog=32, tenants=4)
    _served_chaos_point()
    for n in (16, 64, 256):
        _latency_point(n)


def run_smoke() -> None:
    """Tiny-shape subset for the per-PR CI job: one point per sweep, with
    the non-identity gather path exercised (and its equivalence asserted)
    on every change.  The perf-ratio gates are off — tiny shapes on shared
    2-core CI runners flake; the local/nightly ``run()`` asserts the real
    bounds — the ratios are still emitted for the uploaded artifact.  The
    fairness sweeps' weight-ratio gates (single-lane AND cross-lane) and
    the predictive-prefetch hit-rate gate *do* run here: WFQ allocation is
    deterministic scheduler arithmetic and the prefetch clock is injected,
    neither is wall-clock.  The decode
    point likewise keeps only its bit-equality assert (batched lane decode
    == per-tenant loop after unmorphing)."""
    _sweep_point(8, 1, 4)
    _fairness_sweep_point(requests_per_tenant=24, rounds=4)
    _cross_lane_fairness_point(requests_per_tenant=8, rounds=4)
    _prefetch_point(rounds=4)
    _gather_sweep_point(
        batch=16, tenants=4, max_ratio=None, sparse_max_ratio=None, iters=3
    )
    _token_sweep_point(8, 16, 4)
    _decode_sweep_point(
        tenants=4, gen=4, prompt_len=8, min_speedup=None, iters=1
    )
    _recovery_point(backlog=8, tenants=2, iters=2)
    _served_chaos_point(chaos_requests=12, overload_requests=16)
    _latency_point(16)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write results as machine-readable JSON")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-shape subset (the per-PR CI job)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run_smoke() if args.smoke else run()
    if args.json:
        write_json(args.json)
