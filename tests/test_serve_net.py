"""Network front door tests: server + client fleet over real sockets.

In-process tests run the asyncio ``DeliveryServer`` against the
``ClientFleet`` on an ephemeral port; the slow test exercises the real
process lifecycle — ``serve.py --mode serve`` as a subprocess, SIGTERM with
a live backlog, graceful drain to exit 0, snapshot persistence, and a
restart that resumes the same engine id space with zero lost or duplicated
rids.
"""
import asyncio
import os
import threading
import time

import numpy as np
import pytest

from repro.core import ConvGeometry, SessionRegistry
from repro.runtime import (
    AsyncDeliveryEngine, FailureInjector, MoLeDeliveryEngine,
)
from repro.runtime import wire
from repro.runtime.api import DeliveryRequest
from repro.runtime.wire import ProtocolError
from repro.launch.client import ClientFleet, FleetConfig
from repro.launch.server import DeliveryServer

GEOM = ConvGeometry(alpha=2, beta=4, m=6, p=3)


def _front(rng, tenants=3, kappa=2, injector=None, **kw):
    registry = SessionRegistry(GEOM, kappa=kappa, capacity=tenants)
    for i in range(tenants):
        k = rng.standard_normal(
            (GEOM.alpha, GEOM.beta, GEOM.p, GEOM.p)
        ).astype(np.float32) / 4
        registry.register(f"tenant-{i}", k)
    engine = MoLeDeliveryEngine(registry)
    kw.setdefault("max_delay_ms", 5.0)
    return AsyncDeliveryEngine(engine, admission="reject", injector=injector,
                               **kw)


def _run_served(front, body, **server_kw):
    """Start a DeliveryServer on an ephemeral port, run ``body(server)``
    inside the loop, then drain."""
    async def go():
        server = DeliveryServer(front, port=0, **server_kw)
        await server.start()
        try:
            return await body(server)
        finally:
            await server.drain_and_stop(timeout=30.0)

    return asyncio.run(go())


def _fleet_cfg(port, **kw):
    kw.setdefault("requests", 9)
    kw.setdefault("clients", 3)
    kw.setdefault("tenants", 3)
    kw.setdefault("batch", 2)
    kw.setdefault("channels", GEOM.alpha)
    kw.setdefault("image_size", GEOM.m)
    kw.setdefault("trace", "uniform:500")
    return FleetConfig(port=port, **kw)


# ---------------------------------------------------------------------------
# in-process: correctness, shedding, deadlines, exactly-once
# ---------------------------------------------------------------------------

def test_server_requires_reject_admission(rng):
    front = _front(rng)
    try:
        blocking = AsyncDeliveryEngine(front.engine, admission="block")
    except Exception:  # pragma: no cover
        raise
    with pytest.raises(ValueError, match="admission"):
        DeliveryServer(blocking)
    blocking.close()
    front.close()


def test_served_results_match_direct_sessions(rng):
    """Every fleet rid resolves ok, and the payload that crossed the wire is
    the same morphed delivery the tenant's session computes directly."""
    import jax.numpy as jnp

    front = _front(rng)
    payload = rng.standard_normal((2, GEOM.alpha, GEOM.m, GEOM.m)).astype(
        np.float32
    )

    async def body(server):
        reader, writer = await asyncio.open_connection("127.0.0.1",
                                                       server.port)
        req = DeliveryRequest("tenant-1", payload)
        writer.write(wire.encode_request(req, "direct-1"))
        await writer.drain()
        frame = await asyncio.wait_for(wire.read_frame(reader), timeout=30)
        writer.close()
        return frame

    kind, header, body_bytes = _run_served(front, body)
    assert kind == wire.KIND_RES
    res = wire.decode_result(header, body_bytes)
    expected = np.asarray(
        front.registry.session("tenant-1").deliver(jnp.asarray(payload))
    )
    np.testing.assert_allclose(res.payload, expected, rtol=1e-5, atol=1e-5)
    front.close()


def test_fleet_all_resolved_exactly_once(rng):
    front = _front(rng)

    async def body(server):
        return await ClientFleet(_fleet_cfg(server.port)).run()

    report = _run_served(front, body)
    report.assert_exactly_once()
    assert report.counts() == {"ok": 9}
    assert len(report.latencies_ms) == 9
    front.close()


def test_overload_sheds_with_typed_rejections(rng):
    """A burst far past max_pending_rows is answered with OVERLOADED frames,
    not queued into latency collapse: accepted requests stay fast and the
    shed counter matches the rejections the fleet observed."""
    front = _front(rng, max_inflight_rows=4096)

    async def body(server):
        cfg = _fleet_cfg(server.port, requests=24, batch=4,
                         trace="burst:24@1", max_attempts=1)
        return await ClientFleet(cfg).run()

    report = _run_served(front, body, max_pending_rows=8)
    report.assert_exactly_once()
    counts = report.counts()
    assert counts.get("rejected:OVERLOADED", 0) > 0
    assert counts.get("ok", 0) > 0
    assert counts.get("rejected:OVERLOADED", 0) + counts.get("ok", 0) == 24
    assert front.engine.stats.shed_requests == counts["rejected:OVERLOADED"]
    # Accepted requests kept a bounded latency: nothing sat in a swollen
    # queue behind the burst.
    assert report.quantile_ms(0.99) < 10_000
    front.close()


def test_per_tenant_quota_sheds_overloaded(rng):
    """The engine's admission='reject' quota surfaces as the same typed
    OVERLOADED frame as the global cap."""
    front = _front(rng, max_inflight_rows=2)

    async def body(server):
        cfg = _fleet_cfg(server.port, requests=12, batch=2, tenants=1,
                         trace="burst:12@1", max_attempts=1)
        return await ClientFleet(cfg).run()

    report = _run_served(front, body)
    report.assert_exactly_once()
    counts = report.counts()
    assert counts.get("rejected:OVERLOADED", 0) > 0
    assert front.engine.stats.rejected > 0
    front.close()


def test_expired_deadline_rejected_on_arrival(rng):
    """age_ms >= deadline_ms -> typed EXPIRED without touching the engine."""
    front = _front(rng)

    async def body(server):
        reader, writer = await asyncio.open_connection("127.0.0.1",
                                                       server.port)
        req = DeliveryRequest(
            "tenant-0",
            np.zeros((1, GEOM.alpha, GEOM.m, GEOM.m), np.float32),
            deadline_ms=50.0,
        )
        writer.write(wire.encode_request(req, "late-1", age_ms=80.0))
        await writer.drain()
        frame = await asyncio.wait_for(wire.read_frame(reader), timeout=30)
        writer.close()
        return frame

    kind, header, _ = _run_served(front, body)
    assert kind == wire.KIND_REJ
    rej = wire.decode_reject(header)
    assert rej.code == "EXPIRED"
    assert front.engine.stats.expired_requests == 1
    front.close()


def test_unknown_tenant_rejected_invalid(rng):
    front = _front(rng)

    async def body(server):
        cfg = _fleet_cfg(server.port, requests=3, tenants=1, max_attempts=1)
        cfg = FleetConfig(**{**cfg.__dict__, "fleet_id": "bad"})
        fleet = ClientFleet(cfg)
        fleet._make_request = lambda idx: DeliveryRequest(
            "no-such-tenant",
            np.zeros((1, GEOM.alpha, GEOM.m, GEOM.m), np.float32),
        )
        return await fleet.run()

    report = _run_served(front, body)
    report.assert_exactly_once()
    assert report.counts() == {"rejected:INVALID": 3}
    front.close()


def test_duplicate_rid_served_from_cache(rng):
    """A retry of a completed rid is answered from the result cache — the
    engine never sees it twice, and the bytes agree with the original."""
    front = _front(rng)

    async def body(server):
        reader, writer = await asyncio.open_connection("127.0.0.1",
                                                       server.port)
        req = DeliveryRequest(
            "tenant-2",
            np.ones((1, GEOM.alpha, GEOM.m, GEOM.m), np.float32),
        )
        frame = wire.encode_request(req, "dup-1")
        writer.write(frame)
        await writer.drain()
        first = await asyncio.wait_for(wire.read_frame(reader), timeout=30)
        writer.write(frame)               # identical retry, same rid
        await writer.drain()
        second = await asyncio.wait_for(wire.read_frame(reader), timeout=30)
        writer.close()
        return first, second

    (k1, h1, p1), (k2, h2, p2) = _run_served(front, body)
    assert k1 == k2 == wire.KIND_RES
    r1, r2 = wire.decode_result(h1, p1), wire.decode_result(h2, p2)
    assert r1.engine_rid == r2.engine_rid      # one engine delivery, not two
    np.testing.assert_array_equal(r1.payload, r2.payload)
    assert front.engine.stats.duplicate_hits == 1
    front.close()


async def _read_raw_frame(reader) -> bytes:
    """One whole frame as the bytes that crossed the socket."""
    head = await asyncio.wait_for(reader.readexactly(wire._HEAD.size), 30)
    _, _, hlen, plen = wire._HEAD.unpack(head)
    return head + await asyncio.wait_for(reader.readexactly(hlen + plen), 30)


def _vision_request(tenant="tenant-0", batch=2, seed=0):
    x = np.random.default_rng(seed).standard_normal(
        (batch, GEOM.alpha, GEOM.m, GEOM.m)).astype(np.float32)
    return DeliveryRequest(tenant, x)


def test_result_sent_as_parts_arrives_byte_identical(rng):
    """The head and the view the server writes with writelines reach a
    real client over loopback as the bytes of the joined frame."""
    front = _front(rng)
    req = _vision_request("tenant-1")

    async def body(server):
        reader, writer = await asyncio.open_connection("127.0.0.1",
                                                       server.port)
        writer.write(wire.encode_request(req, "parts-1"))
        await writer.drain()
        raw = await _read_raw_frame(reader)
        writer.close()
        return raw, server._completed["parts-1"]

    raw, cached = _run_served(front, body)
    assert isinstance(cached, tuple) and isinstance(cached[1], memoryview)
    assert raw == b"".join(cached)
    res = wire.decode_result(*wire.decode_frame(raw)[1:])
    assert res.payload.shape == (2, GEOM.beta, GEOM.m, GEOM.m)
    assert front.engine.stats.result_frames_by_view == 1
    front.close()


def test_retry_from_the_result_cache_resends_the_same_bytes(rng):
    front = _front(rng)
    frame = wire.encode_request(_vision_request("tenant-2"), "again-1")

    async def body(server):
        reader, writer = await asyncio.open_connection("127.0.0.1",
                                                       server.port)
        raws = []
        for _ in range(2):                # the retry re-sends the same rid
            writer.write(frame)
            await writer.drain()
            raws.append(await _read_raw_frame(reader))
        writer.close()
        return raws

    first, second = _run_served(front, body)
    assert first == second
    assert wire.decode_frame(first)[0] == wire.KIND_RES
    assert front.engine.stats.duplicate_hits == 1
    assert front.engine.stats.result_frames_by_view == 2
    front.close()


def test_chaos_truncated_result_fails_typed_then_refetches(rng):
    """The chaos write cuts a result frame sent as parts: the client's
    reader fails with a typed ProtocolError, and a retry on a fresh
    connection is answered from the cache with the whole frame."""
    inj = FailureInjector(network_phases={"write"}, network_rate=1.0, seed=0)
    front = _front(rng)
    frame = wire.encode_request(_vision_request("tenant-0"), "cut-1")

    async def exchange(port, read):
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(frame)
        await writer.drain()
        try:
            return await asyncio.wait_for(read(reader), 30)
        finally:
            writer.close()

    async def body(server):
        cut = await exchange(server.port, lambda r: r.read())   # to EOF
        inj.network_phases = set()        # the fault has fired: stand down
        again = await exchange(server.port, wire.read_frame)
        seen = asyncio.StreamReader()     # what the client's reader got
        seen.feed_data(cut)
        seen.feed_eof()
        with pytest.raises(ProtocolError, match="truncated frame"):
            await wire.read_frame(seen)
        return cut, again, b"".join(server._completed["cut-1"])

    cut, (kind, header, payload), whole = _run_served(front, body,
                                                      injector=inj)
    assert cut == whole[: len(whole) // 2]      # half of the joined frame
    assert kind == wire.KIND_RES
    assert wire.decode_frame(whole)[1:] == (header, payload)
    assert inj.network_hits == {"write": 1}
    assert front.engine.stats.duplicate_hits == 1
    assert front.engine.stats.result_frames_by_view == 1
    front.close()


def test_result_frames_by_view_counts_the_ok_frames(rng):
    """Without hedges or retries, one result frame by view is written for
    each ok outcome; rejects are plain frames and are not counted."""
    front = _front(rng, max_inflight_rows=4096)

    async def body(server):
        cfg = _fleet_cfg(server.port, requests=24, batch=4,
                         trace="burst:24@1", max_attempts=1,
                         attempt_timeout_ms=60_000.0)
        return await ClientFleet(cfg).run()

    report = _run_served(front, body, max_pending_rows=8)
    report.assert_exactly_once()
    counts = report.counts()
    assert counts.get("ok", 0) > 0
    assert counts.get("rejected:OVERLOADED", 0) > 0
    assert front.engine.stats.result_frames_by_view == counts["ok"]
    front.close()


def test_garbage_frame_closes_connection_not_server(rng):
    """A stream that violates the protocol loses its connection; the accept
    loop and a well-behaved client are unaffected."""
    front = _front(rng)

    async def body(server):
        # Garbage stream: server must close it.
        r1, w1 = await asyncio.open_connection("127.0.0.1", server.port)
        w1.write(b"this is not a delivery frame at all.....")
        await w1.drain()
        eof = await asyncio.wait_for(r1.read(), timeout=30)
        assert eof == b""
        w1.close()
        # The server still serves.
        report = await ClientFleet(
            _fleet_cfg(server.port, requests=3)
        ).run()
        return report

    report = _run_served(front, body)
    report.assert_exactly_once()
    assert report.counts() == {"ok": 3}
    assert front.engine.stats.reconnects >= 1
    front.close()


def test_exactly_once_under_chaos_with_flusher_crash(rng):
    """The acceptance-run shape, in miniature: server-side network chaos
    (dropped accepts, lost reads, truncated/stalled writes), client-side
    chaos (truncated requests, dropped connections), and one injected
    flusher crash — every rid still resolves exactly once, with no
    mismatched duplicate payloads."""
    inj = FailureInjector(
        at_phases={"device"},              # one-shot flusher crash
        network_phases={"accept", "read", "write", "stall"},
        network_rate=0.12, stall_ms=50.0, seed=5,
    )
    front = _front(rng, injector=inj)

    async def body(server):
        client_inj = FailureInjector(
            network_phases={"write", "read", "stall"},
            network_rate=0.12, stall_ms=50.0, seed=6,
        )
        cfg = _fleet_cfg(server.port, requests=18, clients=4,
                         trace="uniform:300", chaos=client_inj,
                         attempt_timeout_ms=1000.0, timeout_ms=45000.0,
                         max_attempts=8)
        return await ClientFleet(cfg).run()

    report = _run_served(front, body, injector=inj, read_timeout=3.0)
    report.assert_exactly_once()
    counts = report.counts()
    assert sum(counts.values()) == 18
    assert counts.get("ok", 0) >= 12       # chaos hurts, must not break
    assert report.mismatched_dups == 0
    # The chaos actually bit: retries/hedges happened and the injected
    # flusher crash fired (the supervisor recovered it — all rids resolved).
    assert report.hedges + report.retries + report.conn_drops > 0
    assert "device" in inj.fired
    front.close()


def test_drain_rejects_new_requests_typed(rng):
    front = _front(rng)

    async def body(server):
        # Open the connection *before* drain starts.
        reader, writer = await asyncio.open_connection("127.0.0.1",
                                                       server.port)
        server._draining = True
        req = DeliveryRequest(
            "tenant-0",
            np.zeros((1, GEOM.alpha, GEOM.m, GEOM.m), np.float32),
        )
        writer.write(wire.encode_request(req, "drained-1"))
        await writer.drain()
        frame = await asyncio.wait_for(wire.read_frame(reader), timeout=30)
        writer.close()
        return frame

    kind, header, _ = _run_served(front, body)
    assert kind == wire.KIND_REJ
    assert wire.decode_reject(header).code == "DRAINING"
    front.close()


def test_close_front_exit_code_fails_dead_engine(rng):
    """The server's exit code: 0 for a clean drain, 1 for lost rids, and 1
    for a flusher that died, even though its failed requests left nothing
    in flight for the drain to lose."""
    from repro.launch.server import close_front
    from repro.runtime.async_engine import EngineDeadError

    assert close_front(_front(rng), lost=0) == 0
    assert close_front(_front(rng), lost=2) == 1
    dead = _front(rng, injector=FailureInjector(at_phases={"device"}),
                  max_restarts=0)
    fut = dead.submit(DeliveryRequest(
        "tenant-0", np.zeros((1, GEOM.alpha, GEOM.m, GEOM.m), np.float32)
    ))
    with pytest.raises(EngineDeadError):
        fut.result(timeout=60)
    assert dead.failure is not None
    assert close_front(dead, lost=0) == 1


# ---------------------------------------------------------------------------
# slow lane: the real process lifecycle
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_sigterm_drain_snapshot_restart_exactly_once(tmp_path):
    """SIGTERM a served engine with a live backlog: it drains gracefully
    (every accepted rid answered), persists a snapshot, exits 0; a restart
    restores the snapshot and resumes the same id space — across both runs,
    zero rids lost, zero engine ids duplicated."""
    from repro.launch.client import run_fleet, spawn_server, stop_server

    snap = str(tmp_path / "snap")
    server_flags = [
        "--tenants", "3", "--kappa", "2",
        "--channels", str(GEOM.alpha), "--out-channels", str(GEOM.beta),
        "--image-size", str(GEOM.m), "--warm-batch", "2",
        "--snapshot-dir", snap,
    ]
    proc, port = spawn_server(server_flags)
    cfg = FleetConfig(
        port=port, requests=14, clients=3, tenants=3, batch=2,
        channels=GEOM.alpha, image_size=GEOM.m, trace="uniform:40",
        timeout_ms=6000.0, max_attempts=3,
    )
    box = {}

    def drive():
        box["report"] = asyncio.run(run_fleet(cfg))

    t = threading.Thread(target=drive)
    t.start()
    # SIGTERM mid-run: some requests are in flight, some not yet launched
    # (the 40 rps open loop spreads 14 requests over ~350ms).
    time.sleep(0.15)
    rc = stop_server(proc, timeout=90.0)
    t.join(timeout=120.0)
    assert not t.is_alive()
    assert rc == 0, proc.stdout.read()

    r1 = box["report"]
    r1.assert_exactly_once()
    c1 = r1.counts()
    # Everything the server accepted was answered; later arrivals got a
    # typed DRAINING rejection or timed out against a gone server — but
    # nothing was silently lost.
    assert c1.get("ok", 0) >= 1
    assert sum(c1.values()) == 14
    # The drain persisted a snapshot.
    steps = [p for p in os.listdir(snap) if not p.endswith(".tmp")]
    assert steps, "graceful drain did not persist a snapshot"
    max_rid_1 = max(r1.engine_rids.values())

    # Restart on the same snapshot dir: same id space, fresh port.
    proc, port = spawn_server(server_flags)
    cfg2 = FleetConfig(
        port=port, requests=6, clients=2, tenants=3, batch=2,
        channels=GEOM.alpha, image_size=GEOM.m, trace="uniform:200",
        fleet_id="f1",
    )
    box2 = {}
    threading.Thread(
        target=lambda: box2.update(report=asyncio.run(run_fleet(cfg2)))
    ).run()
    rc = stop_server(proc, timeout=90.0)
    assert rc == 0, proc.stdout.read()

    r2 = box2["report"]
    r2.assert_exactly_once()
    assert r2.counts() == {"ok": 6}
    # Id-space continuity: no engine rid from run 2 collides with run 1.
    assert min(r2.engine_rids.values()) > max_rid_1
