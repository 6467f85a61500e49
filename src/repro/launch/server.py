"""Streaming network front door: the delivery engine behind a real wire.

``DeliveryServer`` serves the typed delivery API over asyncio TCP with the
length-prefixed frame codec (``repro.runtime.wire``), driving an
:class:`~repro.runtime.AsyncDeliveryEngine` (background deadline flusher +
per-tenant admission control).  Overload safety is the design center — the
server degrades by *typed rejection*, never by queueing into latency
collapse or silently dropping work:

  * **Load shedding** — a request that would push admitted-but-uncompleted
    rows past ``max_pending_rows`` (or its tenant past the engine's
    admission quota — the front door is constructed ``admission="reject"``)
    is answered with an ``OVERLOADED`` rejection frame immediately.
    Accepted requests keep their deadline-flusher latency; shed requests
    cost one frame round trip.
  * **Deadline propagation** — a request that arrives already past its
    ``deadline_ms`` (client-side age + nothing left to spend) is rejected
    ``EXPIRED`` without touching the engine; otherwise the *remaining*
    budget is what the engine's deadline flusher schedules against.
  * **Slow/stalled clients** — each connection runs its own reader/writer
    tasks with read/write timeouts; a client that stalls mid-frame or stops
    draining responses loses *its* connection (its completed results stay in
    the exactly-once cache for the retry) while the accept loop and every
    other connection keep running.
  * **Exactly-once retries** — requests carry a client-chosen correlation id
    (``rid``); retries and hedges re-send under the same rid.  The server
    tracks in-flight rids (a duplicate attaches as a second waiter, it does
    not resubmit) and caches completed frames (a retry after a lost response
    is answered from cache), so a request is delivered by the engine at most
    once however many times the fleet re-sends it.
  * **Results by view** — a result frame is its head and header plus a
    read-only view of the result array (``wire.encode_result_parts``),
    handed to the socket with ``writelines``: the payload is never copied
    on the event loop, and the cache keeps the array, not a copy of it.
  * **Graceful drain** — SIGTERM stops the accept loop, lets the engine
    flush every admitted request, writes all pending responses, notifies
    clients (``BYE``), persists an :class:`EngineSnapshot` when
    ``snapshot_dir`` is configured, and exits 0 with zero lost rids; a
    restarting server restores the snapshot and resumes the same engine id
    space.
  * **Chaos** — a :class:`~repro.runtime.FailureInjector` with network
    phases (``accept``/``read``/``write``/``stall``) makes the server
    misbehave on purpose: dropped fresh connections, requests lost after
    read, truncated response frames, stalled writes.  The client fleet
    (``repro.launch.client``) must still resolve every rid exactly once.

Counters land in ``EngineStats`` (``shed_requests``, ``expired_requests``,
``reconnects``, ``duplicate_hits``, ``result_frames_by_view``), next to a
per-tenant security-budget line computed from ``repro.core.security`` at
registration time — the operator sees the privacy budget of the served
tenants beside their latency budget.
"""
from __future__ import annotations

import asyncio
import collections
import contextlib
import dataclasses
import logging
import os
import signal
import sys
import time

import numpy as np

from repro.runtime import wire
from repro.runtime.async_engine import (
    AdmissionError, AsyncDeliveryEngine, EngineDeadError,
)
from repro.runtime.tracing import span
from repro.runtime.wire import ProtocolError

__all__ = [
    "DeliveryServer", "build_front", "close_front", "developer_kernels",
    "make_server", "run_serve",
]

_log = logging.getLogger(__name__)

# Rejection codes worth caching: deterministic outcomes a retry of the same
# bytes cannot change.  OVERLOADED / DRAINING are transient by definition —
# caching them would turn a momentary shed into a permanent one.
_CACHEABLE_REJECTS = ("EXPIRED", "INVALID", "FAILED")

# One response frame: ``bytes`` (rejects, BYE) or, for a result, the buffers
# of ``wire.encode_result_parts`` to be written in order.
_Frame = bytes | tuple[bytes, memoryview]


class _Conn:
    """One client connection: reader/writer stream + outgoing frame queue."""

    __slots__ = ("reader", "writer", "out", "alive", "peer")

    def __init__(self, reader, writer, out_frames: int):
        self.reader = reader
        self.writer = writer
        self.out: asyncio.Queue = asyncio.Queue(maxsize=out_frames)
        self.alive = True
        try:
            self.peer = writer.get_extra_info("peername")
        except Exception:  # pragma: no cover - transport quirk
            self.peer = None


class DeliveryServer:
    """Asyncio TCP front door over an :class:`AsyncDeliveryEngine`.

    Parameters
    ----------
    front:
        The async engine, constructed with ``admission="reject"`` — shedding
        must be a typed response, not submitter backpressure that would
        block the event loop.
    max_pending_rows:
        Global shed threshold: admitted-but-uncompleted rows across all
        tenants.  0 disables the global cap (per-tenant quotas still hold).
    read_timeout / write_timeout:
        Per-connection I/O timeouts (seconds).  A connection that stalls
        mid-frame or stops draining responses is closed; the engine and the
        other connections never wait on it.
    result_cache:
        Completed frames retained for retry deduplication (LRU, per wire
        rid).
    injector:
        Optional :class:`FailureInjector` with ``network_phases`` armed —
        server-side chaos for fleet tests.
    """

    def __init__(
        self,
        front: AsyncDeliveryEngine,
        *,
        host: str = "127.0.0.1",
        port: int = 0,
        max_pending_rows: int = 4096,
        read_timeout: float = 30.0,
        write_timeout: float = 10.0,
        max_frame_bytes: int = wire.DEFAULT_MAX_FRAME,
        result_cache: int = 4096,
        out_frames: int = 256,
        injector=None,
    ):
        if front.admission != "reject":
            raise ValueError(
                "DeliveryServer requires admission='reject': overload must "
                "surface as a typed OVERLOADED frame, not as backpressure "
                "blocking the event loop"
            )
        self.front = front
        self.host = host
        self.port = int(port)
        self.max_pending_rows = int(max_pending_rows)
        self.read_timeout = float(read_timeout)
        self.write_timeout = float(write_timeout)
        self.max_frame_bytes = int(max_frame_bytes)
        self.result_cache = int(result_cache)
        self.out_frames = int(out_frames)
        self.injector = injector

        self._server: asyncio.AbstractServer | None = None
        self._loop: asyncio.AbstractEventLoop | None = None
        self._conns: dict[_Conn, asyncio.Task] = {}       # conn -> writer task
        self._inflight: dict[str, set[_Conn]] = {}        # wire rid -> waiters
        self._completed: collections.OrderedDict[str, _Frame] = (
            collections.OrderedDict()
        )
        self._draining = False

    # -- stats shorthand ------------------------------------------------------
    @property
    def stats(self):
        return self.front.engine.stats

    # -- lifecycle ------------------------------------------------------------
    async def start(self) -> None:
        self._loop = asyncio.get_running_loop()
        self._server = await asyncio.start_server(
            self._on_conn, self.host, self.port
        )
        self.port = self._server.sockets[0].getsockname()[1]

    async def __aenter__(self) -> "DeliveryServer":
        await self.start()
        return self

    async def __aexit__(self, *exc) -> None:
        await self.drain_and_stop()

    async def drain_and_stop(self, timeout: float = 30.0) -> int:
        """Graceful drain: stop accepting, flush the admitted backlog, write
        every pending response, notify + close connections.  Returns the
        number of wire rids still unresolved at timeout (0 on a clean
        drain)."""
        self._draining = True
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()
        deadline = time.monotonic() + timeout
        # Engine side: force the flusher and wait for every admitted request
        # to publish.  front.drain blocks, so it runs off-loop — completion
        # callbacks keep landing on the loop meanwhile.
        self.front.flush_now()
        with contextlib.suppress(TimeoutError, EngineDeadError):
            await asyncio.get_running_loop().run_in_executor(
                None, lambda: self.front.drain(timeout=timeout)
            )
        # Wire side: _complete callbacks for the drained futures may still be
        # queued on the loop; yield until every in-flight rid resolved.
        while self._inflight and time.monotonic() < deadline:
            await asyncio.sleep(0.01)
        lost = len(self._inflight)
        # Flush + close every connection: BYE then a sentinel — the writer
        # task drains the queue in order, so all responses hit the socket
        # before the stream ends.
        for conn in list(self._conns):
            if conn.alive:
                self._send(conn, wire.encode_bye("drain"))
            with contextlib.suppress(asyncio.QueueFull):
                conn.out.put_nowait(None)
        if self._conns:
            await asyncio.wait(
                list(self._conns.values()), timeout=self.write_timeout
            )
        for conn in list(self._conns):
            self._close_conn(conn, count_reconnect=False)
        # Durable id space for restart-with-restore.
        if self.front._snapshotter is not None:
            with contextlib.suppress(EngineDeadError):
                await asyncio.get_running_loop().run_in_executor(
                    None, self.front.snapshot_now
                )
        return lost

    # -- connection handling --------------------------------------------------
    async def _on_conn(self, reader, writer) -> None:
        if self._draining or (
            self.injector is not None and self.injector.network_hit("accept")
        ):
            # Drain: no new streams.  Chaos: a connection dropped the moment
            # it is accepted — the client sees a reset and retries.
            if not self._draining:
                self.stats.reconnects += 1
            writer.close()
            return
        conn = _Conn(reader, writer, self.out_frames)
        self._conns[conn] = asyncio.ensure_future(self._writer_loop(conn))
        try:
            while True:
                frame = await asyncio.wait_for(
                    wire.read_frame(reader, self.max_frame_bytes),
                    timeout=self.read_timeout,
                )
                if frame is None:        # clean EOF: client closed
                    break
                kind, header, payload = frame
                if kind == wire.KIND_BYE:
                    break
                if kind != wire.KIND_REQ:
                    raise ProtocolError(
                        f"unexpected frame kind {kind} from a client"
                    )
                self._on_request(conn, header, payload)
        except (asyncio.TimeoutError, ProtocolError, ConnectionError, OSError):
            # Stalled mid-frame, garbage, or a reset: this connection is
            # done — the engine, the accept loop, and every other client
            # are unaffected, and completed results stay cached for the
            # retry on a fresh connection.
            if conn.alive:
                self.stats.reconnects += 1
        finally:
            self._close_conn(conn, count_reconnect=False)

    def _close_conn(self, conn: _Conn, count_reconnect: bool = True) -> None:
        if conn.alive and count_reconnect:
            self.stats.reconnects += 1
        conn.alive = False
        wtask = self._conns.pop(conn, None)
        if wtask is not None and not wtask.done():
            wtask.cancel()
        with contextlib.suppress(Exception):
            conn.writer.close()

    async def _writer_loop(self, conn: _Conn) -> None:
        inj = self.injector
        try:
            while True:
                frame = await conn.out.get()
                if frame is None:
                    with contextlib.suppress(
                        asyncio.TimeoutError, ConnectionError, OSError
                    ):
                        await asyncio.wait_for(
                            conn.writer.drain(), self.write_timeout
                        )
                    break
                if inj is not None and inj.network_hit("stall"):
                    await asyncio.sleep(inj.stall_ms / 1e3)
                if inj is not None and inj.network_hit("write"):
                    # Chaos: truncate the frame mid-write and reset — the
                    # client's reader must fail with a typed ProtocolError
                    # (or EOF) and re-fetch from the result cache.
                    if isinstance(frame, tuple):
                        frame = b"".join(frame)
                    conn.writer.write(frame[: max(1, len(frame) // 2)])
                    raise ConnectionResetError("chaos: truncated write")
                with span("mole.server.write"):
                    if isinstance(frame, tuple):
                        conn.writer.writelines(frame)
                        self.stats.result_frames_by_view += 1
                    else:
                        conn.writer.write(frame)
                await asyncio.wait_for(conn.writer.drain(), self.write_timeout)
        except (asyncio.TimeoutError, ConnectionError, OSError):
            pass
        except asyncio.CancelledError:  # _close_conn
            raise
        finally:
            if conn.alive:
                conn.alive = False
                self.stats.reconnects += 1
                with contextlib.suppress(Exception):
                    conn.writer.close()

    # -- request path ---------------------------------------------------------
    def _send(self, conn: _Conn, frame: _Frame) -> None:
        if not conn.alive:
            return
        try:
            conn.out.put_nowait(frame)
        except asyncio.QueueFull:
            # A client that stopped draining responses: closing it is the
            # bounded-memory answer; its results stay cached for the retry.
            self._close_conn(conn)

    def _finish_now(self, conn: _Conn, rid: str, frame: bytes,
                    code: str | None = None) -> None:
        if code in _CACHEABLE_REJECTS:
            self._remember(rid, frame)
        self._send(conn, frame)

    def _remember(self, rid: str, frame: _Frame) -> None:
        self._completed[rid] = frame
        self._completed.move_to_end(rid)
        while len(self._completed) > self.result_cache:
            self._completed.popitem(last=False)

    def _on_request(self, conn: _Conn, header: dict, payload: bytes) -> None:
        stats = self.stats
        rid = header.get("rid")
        if not isinstance(rid, str) or not rid:
            raise ProtocolError(f"request frame without a rid (got {rid!r})")
        if self.injector is not None and self.injector.network_hit("read"):
            # Chaos: the request was read off the socket and then lost
            # before processing — exactly the window a crash-between-read-
            # and-submit opens.  The client's hedge/retry must cover it.
            return
        # Exactly-once: a retry of a completed rid is answered from cache;
        # a retry of an in-flight rid attaches as an extra waiter (hedged
        # duplicate) — neither reaches the engine again.
        cached = self._completed.get(rid)
        if cached is not None:
            stats.duplicate_hits += 1
            self._completed.move_to_end(rid)
            self._send(conn, cached)
            return
        waiters = self._inflight.get(rid)
        if waiters is not None:
            stats.duplicate_hits += 1
            waiters.add(conn)
            return
        try:
            with span("mole.server.decode"):
                _, age_ms, req = wire.decode_request(header, payload)
        except ProtocolError:
            raise                       # stream-level: close the connection
        except (ValueError, TypeError) as e:
            self._finish_now(
                conn, rid, wire.encode_reject(rid, "INVALID", str(e)),
                code="INVALID",
            )
            return
        if self._draining:
            self._finish_now(
                conn, rid,
                wire.encode_reject(rid, "DRAINING", "server is draining"),
                code="DRAINING",
            )
            return
        # Deadline propagation: the client reports how old the request
        # already is; what is left is the engine's budget.  Nothing left ->
        # EXPIRED without touching the engine.
        if req.deadline_ms is not None:
            remaining = req.deadline_ms - age_ms
            if remaining <= 0:
                stats.expired_requests += 1
                self._finish_now(
                    conn, rid,
                    wire.encode_reject(
                        rid, "EXPIRED",
                        f"deadline_ms={req.deadline_ms:g} already "
                        f"{age_ms:.1f}ms old on arrival",
                    ),
                    code="EXPIRED",
                )
                return
            req = dataclasses.replace(req, deadline_ms=remaining)
        # Load shedding, global cap: reject instead of queueing into
        # latency collapse.  (Per-tenant quotas are the engine's
        # admission="reject" below.)
        n_rows = int(req.payload.shape[0]) if req.payload.ndim else 1
        if (
            self.max_pending_rows
            and self.front.inflight_rows() + n_rows > self.max_pending_rows
        ):
            stats.shed_requests += 1
            self._finish_now(
                conn, rid,
                wire.encode_reject(
                    rid, "OVERLOADED",
                    f"{self.front.inflight_rows()} rows in flight "
                    f">= max_pending_rows={self.max_pending_rows}",
                ),
            )
            return
        try:
            fut = self.front.submit(req)
        except AdmissionError as e:
            stats.shed_requests += 1
            self._finish_now(
                conn, rid, wire.encode_reject(rid, "OVERLOADED", str(e))
            )
            return
        except (KeyError, ValueError, TypeError) as e:
            self._finish_now(
                conn, rid, wire.encode_reject(rid, "INVALID", str(e)),
                code="INVALID",
            )
            return
        except (EngineDeadError, RuntimeError) as e:
            self._finish_now(
                conn, rid, wire.encode_reject(rid, "FAILED", str(e)),
                code="FAILED",
            )
            return
        self._inflight[rid] = {conn}
        fut.add_done_callback(
            lambda f, rid=rid: self._schedule_complete(rid, f)
        )

    def _schedule_complete(self, rid: str, fut) -> None:
        # Runs on the flusher thread: hop back onto the event loop.  A loop
        # already closed (hard shutdown) simply drops the completion — the
        # result is gone with the process anyway.
        try:
            self._loop.call_soon_threadsafe(self._complete, rid, fut)
        except RuntimeError:  # pragma: no cover - loop torn down
            pass

    def _complete(self, rid: str, fut) -> None:
        waiters = self._inflight.pop(rid, set())
        if fut.cancelled():
            return
        code = None
        exc = fut.exception()
        if exc is None:
            try:
                with span("mole.server.encode"):
                    frame = wire.encode_result_parts(rid, fut.result())
            except ProtocolError as e:  # pragma: no cover - non-wire dtype
                frame, code = wire.encode_reject(rid, "FAILED", str(e)), "FAILED"
        elif isinstance(exc, AdmissionError):
            frame = wire.encode_reject(rid, "OVERLOADED", str(exc))
            self.stats.shed_requests += 1
        else:
            frame, code = wire.encode_reject(rid, "FAILED", str(exc)), "FAILED"
        if code is None and exc is None:
            self._remember(rid, frame)
        elif code in _CACHEABLE_REJECTS:
            self._remember(rid, frame)
        for conn in waiters:
            self._send(conn, frame)


# ---------------------------------------------------------------------------
# CLI driver (serve.py --mode serve)
# ---------------------------------------------------------------------------

def developer_kernels(geom, tenants: int, seed: int) -> list[np.ndarray]:
    """Each served tenant's first-layer conv kernels ``(alpha, beta, p, p)``,
    drawn from ``seed`` at a 1/sqrt(fan-in) scale, in tenant order."""
    rng = np.random.default_rng(seed)
    fan_in = geom.alpha * geom.p * geom.p
    return [
        rng.standard_normal((geom.alpha, geom.beta, geom.p, geom.p))
        .astype(np.float32) / np.sqrt(fan_in)
        for _ in range(tenants)
    ]


def lm_config(args):
    """The LM configuration ``--arch`` names (``--smoke``: its reduced
    test size), cut to ``--layers`` layers when given: the first layers of
    the published stack, every width as published."""
    import dataclasses

    from repro.configs import get_config, get_smoke_config

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.layers is not None:
        fixed = len(cfg.prefix_pattern) + len(cfg.suffix_pattern)
        groups, rem = divmod(args.layers - fixed, len(cfg.block_pattern))
        if groups < 1 or rem:
            raise ValueError(
                f"--layers {args.layers} cannot be {fixed} unscanned layers "
                f"plus whole groups of {len(cfg.block_pattern)}"
            )
        cfg = dataclasses.replace(cfg, n_groups=groups)
    return cfg


def _build_lm_engine(args):
    """LM tenants: the developer's seeded model (``--arch`` at ``--seed``),
    its embedding and untied head registered for ``--tenants`` tenants, each
    with its own secret vocab permutation; a decode lane of ``--rows`` rows
    over the trunk alone; one warm generation per tenant.  (No security
    budget line: the vocab permutation is a substitution cipher, see
    ``core.security``.)"""
    import jax

    from repro.core.lm import LMSessionRegistry
    from repro.launch.serve import _weights_of
    from repro.launch.steps import layer_params, rows_supported
    from repro.models.api import Model
    from repro.runtime import (
        ContinuousDecodeLane, DeliveryRequest, MoLeDeliveryEngine,
    )

    cfg = lm_config(args)
    if not rows_supported(cfg) or cfg.tie_embeddings:
        raise ValueError(f"{cfg.name} cannot be served by the decode lane")
    model = Model(cfg)
    params = model.init(jax.random.key(args.seed))
    # The trunk stays on the device; the embedding and head go to the
    # tenants' fused tables only.
    embed = np.asarray(params.pop("embed"), np.float32)
    head = np.asarray(params.pop("head"), np.float32)
    params = layer_params(params, cfg, free=True)
    capacity = args.capacity if args.capacity is not None else args.tenants
    registry = LMSessionRegistry(cfg.vocab, cfg.d_model, capacity=capacity)
    weights = _weights_of(args, args.tenants)
    for i in range(args.tenants):
        registry.register(f"tenant-{i}", embed, weight=weights[i], head=head)
    del embed, head
    buckets = tuple(int(b) for b in args.prompt_buckets.split(","))
    engine = MoLeDeliveryEngine(
        lm_registry=registry, backend=args.backend or None,
        max_rows=8, row_buckets=(1, 2, 4, 8), seq_buckets=buckets,
    )
    engine.attach_decode(ContinuousDecodeLane(
        model, params, registry, rows=args.rows, max_len=args.max_len,
        backend=args.backend or None, scheduler=engine.scheduler,
        prompt_buckets=buckets,
    ))
    warm = [
        engine.submit(DeliveryRequest(
            f"tenant-{i}", np.zeros(buckets[0], np.int32), lane="decode",
            max_new_tokens=2,
        ))
        for i in range(args.tenants)
    ]
    return engine, warm, {}


def _build_vision_engine(args):
    """Vision tenants at ``--channels``/``--image-size``/``--out-channels``:
    each its developer's seeded kernels, secret morph core and channel
    permutation; a warm request of ``--warm-batch`` images per tenant."""
    from repro.core import ConvGeometry, SessionRegistry
    from repro.core.security import log2_p_m_bruteforce
    from repro.runtime import DeliveryRequest, MoLeDeliveryEngine

    geom = ConvGeometry(alpha=args.channels, beta=args.out_channels,
                        m=args.image_size, p=3)
    capacity = args.capacity if args.capacity is not None else args.tenants
    registry = SessionRegistry(geom, kappa=args.kappa, capacity=capacity)
    from repro.launch.serve import _weights_of

    weights = _weights_of(args, args.tenants)
    kernels = developer_kernels(geom, args.tenants, args.seed)
    for i, k in enumerate(kernels):
        registry.register(f"tenant-{i}", k, weight=weights[i])

    engine = MoLeDeliveryEngine(registry, backend=args.backend or None)
    # Security budget on the served path: the brute-force attack-success
    # bound for each tenant's morphing secrets (paper §4.2), so --stats
    # reports privacy next to latency.
    budget = {
        t: log2_p_m_bruteforce(sigma=0.5, alpha=geom.alpha, m=geom.m,
                               kappa=args.kappa)
        for t in registry.tenant_ids
    }
    warm = [
        engine.submit(DeliveryRequest(
            f"tenant-{i}",
            np.zeros((args.warm_batch, geom.alpha, geom.m, geom.m), np.float32),
        ))
        for i in range(args.tenants)
    ]
    return engine, warm, budget


def build_front(args) -> AsyncDeliveryEngine:
    """Build registry + engine + async front door from serve.py flags:
    register ``--tenants`` tenants (LM tenants with a decode lane when
    ``--arch`` names a model, vision tenants otherwise), warm the flush path
    so the first served request doesn't pay compilation, restore the latest
    snapshot when ``--snapshot-dir`` holds one (same id space across
    restarts), and fill the per-tenant security-budget line."""
    from repro.runtime import EngineStats, FailureInjector

    engine, warm, budget = (_build_lm_engine(args) if args.arch
                            else _build_vision_engine(args))
    # Warm the buckets the fleet's steady state will hit, so served latency
    # is the flush, not XLA compilation.
    engine.flush()
    for rid in warm:
        engine.take(rid)
    engine.stats = EngineStats()
    engine.stats.service_share_fn = engine.scheduler.service_share

    injector = None
    if args.inject_failure or args.chaos:
        injector = FailureInjector(
            at_phases={args.inject_failure} if args.inject_failure else set(),
            network_phases=(
                {"accept", "read", "write", "stall"} if args.chaos else set()
            ),
            network_rate=args.chaos_rate,
            stall_ms=min(200.0, args.read_timeout_ms / 4),
            seed=args.chaos_seed,
        )
    front = AsyncDeliveryEngine(
        engine,
        max_delay_ms=args.max_delay_ms,
        max_inflight_rows=args.max_inflight_rows,
        admission="reject",
        snapshot_dir=args.snapshot_dir,
        prefetch_horizon_ms=args.prefetch_horizon_ms,
        injector=injector if args.inject_failure else None,
    )
    front.server_injector = injector
    if args.snapshot_dir is not None:
        try:
            replayed = front.restore()
        except FileNotFoundError:
            pass                               # first boot: nothing to restore
        else:
            # Replayed in-flight requests have no wire waiters (their
            # clients will retry under fresh engine rids); what matters is
            # the id space resumed — report and let the flusher deliver
            # them into the futures we drop.
            print(f"restored snapshot: {len(replayed)} in-flight rids "
                  f"replayed, id space resumed", flush=True)
    engine.stats.security_budget_log2.update(budget)
    return front


def make_server(front: AsyncDeliveryEngine, args) -> DeliveryServer:
    """The ``DeliveryServer`` serve.py flags describe, over ``front`` (from
    :func:`build_front`); not started."""
    return DeliveryServer(
        front,
        host=args.host, port=args.port,
        max_pending_rows=args.max_pending_rows,
        read_timeout=args.read_timeout_ms / 1e3,
        write_timeout=args.write_timeout_ms / 1e3,
        injector=front.server_injector,
    )


def run_serve(args) -> dict:
    """serve.py ``--mode serve``: build the front door, serve until
    SIGTERM/SIGINT, drain gracefully, exit 0 with zero lost rids."""
    front = build_front(args)
    server = make_server(front, args)

    async def _amain() -> int:
        await server.start()
        print(f"serving on {server.host}:{server.port} pid={os.getpid()}",
              flush=True)
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGTERM, signal.SIGINT):
            loop.add_signal_handler(sig, stop.set)
        await stop.wait()
        print("drain: SIGTERM/SIGINT received, stopping accepts", flush=True)
        return await server.drain_and_stop(timeout=args.drain_timeout_ms / 1e3)

    lost = asyncio.run(_amain())
    stats = front.engine.stats
    rc = close_front(front, lost)
    if args.stats:
        print("engine stats:")
        for line in stats.summary().splitlines():
            print(f"  {line}")
    print(f"drained: lost_rids={lost} shed={stats.shed_requests} "
          f"expired={stats.expired_requests} reconnects={stats.reconnects} "
          f"duplicate_hits={stats.duplicate_hits}", flush=True)
    if rc:
        sys.exit(rc)
    return {"lost_rids": lost, "shed": stats.shed_requests}


def close_front(front: AsyncDeliveryEngine, lost: int) -> int:
    """Stop the front door after a drain; return the server's exit code.

    Non-zero when the drain left rids unresolved, when the flusher died (its
    in-flight requests were failed, not delivered), or when it would not
    stop.
    """
    try:
        front.close()
    except TimeoutError as e:
        print(f"flusher did not stop: {e}", flush=True)
        return 1
    if front.failure is not None:
        print(f"delivery engine died: {front.failure!r}", flush=True)
        return 1
    return 1 if lost else 0
