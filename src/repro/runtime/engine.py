"""Batched multi-tenant MoLe delivery engine — one plane for vision + LM.

The serving counterpart of :class:`repro.core.protocol.MoLeSession` and
:class:`repro.core.lm.LMSession`: many provider sessions (one per tenant,
each with its own secrets) are registered in slot registries; incoming
requests are coalesced into padded microbatches (``repro.runtime.queue``) and
the provider-side morph plus the developer-side Aug forward run as **one
jitted, mesh-shardable path** over the whole microbatch.  Three lanes share
the machinery:

  * **vision rows** (``SessionRegistry``): block-diagonal morph + Aug-Conv,
      (G, B, F_in) --morph cores[gidx]--> (G, B, F_in) --@ augs[gidx]--> (G, B, F_out)
  * **LM tokens** (``LMSessionRegistry``): per-tenant vocab permutation +
    Aug-Embedding, length-bucketed,
      (G, B, L) --perms[gidx] gather--> (G, B, L) [--AugE[gidx] gather--> (G, B, L, d)]
  * **LM embeddings** (continuous lane): the paper's scheme verbatim with
    ``m^2 -> 1`` — per-position feature rows run through the *same* jitted
    ``_delivery_step`` as the vision lane, with the registry's stacked
    embedding cores and fused input projections as the secrets.

Groups never mix tenants, so tenant A's rows are only ever morphed with
tenant A's secrets — the isolation property asserted in
``tests/test_engine.py`` / ``tests/test_lm_engine.py``.

Kernel backend selection follows ``repro.kernels.dispatch``: the slot-indexed
grouped Pallas kernels (``kernels.grouped``) on TPU, the scan-based jnp
reference on CPU — a flag, not the old hard-coded ``interpret=True``.  Every
lane reads per-tenant secrets **in place** from the stacked ``(S, ...)``
slot arrays (``kernels.ops.morph_rows_grouped`` and friends): there is no
per-microbatch ``secrets[gidx]`` gather copy and no identity-order special
case — out-of-order, duplicate, and partial-table microbatches cost the
same as the slot-ordered steady state.

Under an active mesh the group axis is sharded over the data-parallel axes
(``repro.sharding.rules.delivery_rules`` / ``hints.hint``); on a single
device the hints are no-ops.

**Shape-stable plans.**  Each registry's stacked secrets have a fixed leading
slot dim (``SlotRegistry`` capacity); registration/eviction churn reaches
the device through per-slot ``.at[slot].set`` patches on the cached plan, so
``_delivery_step`` / ``_lm_delivery_step`` are traced at most once per
``(bucket, kappa, backend)`` shape regardless of tenant churn
(``delivery_trace_count`` exposes the trace counter the regression tests
assert on).

**Phase-split flushing.**  :meth:`MoLeDeliveryEngine.flush` is three phases —
:meth:`begin_flush` (coalesce every lane's pending rows into microbatch work
items), :meth:`execute_flush` (run the jitted device steps), and
:meth:`publish_flush` (scatter results back to per-request buffers).  The
sync ``flush()`` just chains them; the async front door calls them
separately so only coalesce/publish run under its lock and the device step
never blocks submitters (``repro.runtime.async_engine``).

This class is **not** thread-safe; ``repro.runtime.async_engine`` layers a
lock, a background deadline flusher, and admission control on top.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import json
import logging
import time
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.d2r import reroll_batch
from repro.core.lm import LMSessionRegistry
from repro.core.protocol import SessionRegistry
from repro.kernels import ref as kref
from repro.kernels.dispatch import resolve_backend
from repro.kernels.ops import (
    aug_conv_forward_grouped,
    aug_embed_grouped,
    morph_rows_grouped,
    token_morph_grouped,
)
from repro.sharding.hints import hint

from . import api
from .api import DeliveryRequest, DeliveryResult
from .prefetch import ArrivalPredictor
from .resilience import EngineSnapshot, StragglerMonitor
from .tracing import span

__all__ = ["EngineStats", "MoLeDeliveryEngine", "delivery_trace_count"]

_log = logging.getLogger(__name__)


def _window_quantile(xs, q: float) -> float:
    if not xs:
        return float("nan")
    xs = sorted(xs)
    idx = min(len(xs) - 1, max(0, int(round(q * (len(xs) - 1)))))
    return xs[idx]


def _fmt_num(x: float, nd: int = 2) -> str:
    """Quantile for summary(): 'n/a' instead of 'nan' when nothing was
    recorded, so an idle engine's stats dump stays readable."""
    return "n/a" if x != x else f"{x:.{nd}f}"


def _fmt_ms(x: float) -> str:
    v = _fmt_num(x)
    return v if v == "n/a" else v + "ms"


# Flush phases timed by the engine; EngineStats keeps one reservoir each,
# and each runs inside the host span mole.flush.<phase> (runtime/tracing.py).
FLUSH_PHASES = ("coalesce", "device", "publish")
_PHASE_SPANS = {p: f"mole.flush.{p}" for p in FLUSH_PHASES}


@dataclasses.dataclass
class PhaseTiming:
    """One flush phase timed by :meth:`EngineStats.phase`: ``ms`` is set
    when the phase ends; ``keep = False`` leaves it out of the reservoir."""

    ms: float | None = None
    keep: bool = True


@dataclasses.dataclass
class EngineStats:
    requests: int = 0
    rows_in: int = 0            # real data rows submitted
    rows_padded: int = 0        # zero rows added by bucketing
    microbatches: int = 0
    flushes: int = 0
    rejected: int = 0           # requests refused by admission control
    blocked: int = 0            # submits that waited on quota backpressure
    # Padding groups whose slot index hit the clamp bound during coalescing:
    # such groups read a real tenant's secrets with all-zero rows (harmless,
    # sliced away) but signal a sparse-table layout CPU serving pays for.
    padding_clamp_count: int = 0
    # Resilience counters: flushes whose device phase the straggler monitor
    # flagged as slow, flush rounds that failed all their waiters, engine
    # snapshots taken, and restores performed.
    degraded_flushes: int = 0
    flush_failures: int = 0
    snapshots: int = 0
    restores: int = 0
    # Submits whose front-door lock wait exceeded stall_threshold_ms: the
    # observable for "the flusher holds the lock across device execution".
    submit_stalls: int = 0
    stall_threshold_ms: float = 1.0
    # Network front door (launch/server.py) counters: requests shed at the
    # door with a typed OVERLOADED rejection (global pending cap or
    # per-tenant admission quota), requests already past their deadline_ms
    # on arrival (EXPIRED), front-door deliver(timeout=) expiries that
    # cancelled their request, connections dropped/reset mid-stream (each
    # one a client reconnect), and retries answered straight from the
    # exactly-once result cache.
    shed_requests: int = 0
    expired_requests: int = 0
    timed_out_requests: int = 0
    reconnects: int = 0
    duplicate_hits: int = 0
    # Per-tenant security budget on the served path: tenant -> log2 of the
    # brute-force attack-success upper bound for the secrets serving that
    # tenant (core.security).  Filled by the network server at registration
    # time; summary() renders it so an operator sees the privacy budget
    # next to the latency budget.
    security_budget_log2: dict = dataclasses.field(default_factory=dict)
    # Predictive prefetch scoreboard: a predicted tenant that next arrives
    # while resident is a hit; a lapsed prediction window (or arriving
    # evicted anyway) is a miss.  The hit rate is the gate on whether the
    # arrival predictor earns its staging bandwidth.
    prefetch_hits: int = 0
    prefetch_misses: int = 0
    # Engine-wire: returns the shared scheduler's per-lane service-unit
    # shares for summary() (None on a bare EngineStats).
    service_share_fn: Callable[[], dict] | None = None
    bucket_shapes: set = dataclasses.field(default_factory=set)
    # Per-tenant admission accounting: how often each tenant was refused
    # (admission="reject") or backpressured (admission="block").
    rejected_by_tenant: collections.Counter = dataclasses.field(
        default_factory=collections.Counter
    )
    blocked_by_tenant: collections.Counter = dataclasses.field(
        default_factory=collections.Counter
    )
    # Completion latencies (ms), submit -> publish, recorded by the engine at
    # publish_flush (and split per request priority when one was given).
    # Bounded reservoir: keeps the most recent window so p50/p95 reflect
    # current traffic, not the whole process lifetime.
    latency_window: int = 4096
    _latencies_ms: collections.deque = dataclasses.field(default=None)
    _latencies_by_priority: dict = dataclasses.field(default=None)
    # Per-flush phase durations (FLUSH_PHASES) + per-submit lock waits +
    # per-request queue waits (enqueue to the coalesce that takes its first
    # rows), same sliding-window reservoirs.
    _phases_ms: dict = dataclasses.field(default=None)
    _submit_wait_ms: collections.deque = dataclasses.field(default=None)
    _queue_wait_ms: collections.deque = dataclasses.field(default=None)
    # WFQ virtual-time lag (max - min across backlogged tenants) sampled at
    # every begin_flush: persistent lag means some tenant is being served far
    # ahead of another relative to its weighted share.
    _wfq_lag: collections.deque = dataclasses.field(default=None)

    def __post_init__(self):
        if self._latencies_ms is None:
            self._latencies_ms = collections.deque(maxlen=self.latency_window)
        if self._latencies_by_priority is None:
            self._latencies_by_priority = {}
        if self._phases_ms is None:
            self._phases_ms = {
                p: collections.deque(maxlen=self.latency_window)
                for p in FLUSH_PHASES
            }
        if self._submit_wait_ms is None:
            self._submit_wait_ms = collections.deque(
                maxlen=self.latency_window
            )
        if self._queue_wait_ms is None:
            self._queue_wait_ms = collections.deque(maxlen=self.latency_window)
        if self._wfq_lag is None:
            self._wfq_lag = collections.deque(maxlen=self.latency_window)

    @property
    def padding_fraction(self) -> float:
        total = self.rows_in + self.rows_padded
        return self.rows_padded / total if total else 0.0

    def record_latency_ms(self, ms: float, priority: int | None = None) -> None:
        self._latencies_ms.append(float(ms))
        if priority is not None:
            bucket = self._latencies_by_priority.get(priority)
            if bucket is None:
                bucket = self._latencies_by_priority[priority] = (
                    collections.deque(maxlen=self.latency_window)
                )
            bucket.append(float(ms))

    def latency_quantile_ms(self, q: float, priority: int | None = None) -> float:
        """Empirical latency quantile in ms over the recent window (nan if
        nothing has been recorded); ``priority`` restricts to requests
        submitted at that priority level."""
        if priority is not None:
            return _window_quantile(
                self._latencies_by_priority.get(priority, ()), q
            )
        return _window_quantile(self._latencies_ms, q)

    @property
    def priorities_seen(self) -> tuple[int, ...]:
        """Priority levels with recorded completion latencies (descending)."""
        return tuple(sorted(self._latencies_by_priority, reverse=True))

    @property
    def p50_ms(self) -> float:
        return self.latency_quantile_ms(0.50)

    @property
    def p95_ms(self) -> float:
        return self.latency_quantile_ms(0.95)

    # -- flush-phase timing ---------------------------------------------------
    def record_phase_ms(self, phase: str, ms: float) -> None:
        self._phases_ms[phase].append(float(ms))

    @contextlib.contextmanager
    def phase(self, name: str):
        """Time one flush phase into its reservoir, inside the host span
        ``mole.flush.<name>``.  Yields a :class:`PhaseTiming`; a phase that
        raises records nothing."""
        timing = PhaseTiming()
        with span(_PHASE_SPANS[name]):
            t0 = time.monotonic()
            yield timing
            timing.ms = (time.monotonic() - t0) * 1e3
        if timing.keep:
            self.record_phase_ms(name, timing.ms)

    def phase_quantile_ms(self, phase: str, q: float) -> float:
        """Per-flush duration quantile of one phase ('coalesce' | 'device' |
        'publish') over the recent window (nan when never flushed)."""
        return _window_quantile(self._phases_ms[phase], q)

    # -- submit-stall accounting ----------------------------------------------
    def record_submit_wait_ms(self, ms: float) -> None:
        """One front-door submit's lock-acquisition wait; waits above
        ``stall_threshold_ms`` count as stalls."""
        self._submit_wait_ms.append(float(ms))
        if ms > self.stall_threshold_ms:
            self.submit_stalls += 1

    def submit_wait_quantile_ms(self, q: float) -> float:
        return _window_quantile(self._submit_wait_ms, q)

    # -- queue waits ----------------------------------------------------------
    def record_queue_wait_ms(self, ms: float) -> None:
        """One request's wait from enqueue to the coalesce that took its
        first rows."""
        self._queue_wait_ms.append(float(ms))

    def queue_wait_quantile_ms(self, q: float) -> float:
        return _window_quantile(self._queue_wait_ms, q)

    # -- WFQ accounting -------------------------------------------------------
    def record_wfq_lag(self, lag: float) -> None:
        """Virtual-time spread across backlogged tenants, sampled per flush."""
        self._wfq_lag.append(float(lag))

    def wfq_lag_quantile(self, q: float) -> float:
        return _window_quantile(self._wfq_lag, q)

    def summary(self) -> str:
        """Multi-line human-readable dump (serve.py --stats).  Degrades
        gracefully — quantiles with no samples print 'n/a', never 'nan'."""
        lines = [
            f"requests={self.requests} rows_in={self.rows_in} "
            f"microbatches={self.microbatches} flushes={self.flushes} "
            f"padding={self.padding_fraction:.0%} "
            f"padding_clamps={self.padding_clamp_count}",
            f"completion latency: p50={_fmt_ms(self.p50_ms)} "
            f"p95={_fmt_ms(self.p95_ms)}",
        ]
        for pr in self.priorities_seen:
            lines.append(
                f"  priority {pr:>3}: "
                f"p50={_fmt_ms(self.latency_quantile_ms(0.5, priority=pr))} "
                f"p95={_fmt_ms(self.latency_quantile_ms(0.95, priority=pr))}"
            )
        for p in FLUSH_PHASES:
            lines.append(
                f"flush {p:>8}: p50={_fmt_ms(self.phase_quantile_ms(p, 0.5))} "
                f"p95={_fmt_ms(self.phase_quantile_ms(p, 0.95))}"
            )
        lines.append(
            f"submit wait: p50={_fmt_ms(self.submit_wait_quantile_ms(0.5))} "
            f"p95={_fmt_ms(self.submit_wait_quantile_ms(0.95))} "
            f"stalls(>{self.stall_threshold_ms:g}ms)={self.submit_stalls}"
        )
        lines.append(
            f"queue wait: p50={_fmt_ms(self.queue_wait_quantile_ms(0.5))} "
            f"p95={_fmt_ms(self.queue_wait_quantile_ms(0.95))}"
        )
        admission = (
            f"admission: rejected={self.rejected} blocked={self.blocked}"
        )
        if self.rejected_by_tenant:
            admission += f" rejects_by_tenant={dict(self.rejected_by_tenant)}"
        if self.blocked_by_tenant:
            admission += f" blocks_by_tenant={dict(self.blocked_by_tenant)}"
        lines.append(admission)
        lines.append(
            f"wfq virtual-time lag: p50={_fmt_num(self.wfq_lag_quantile(0.5))} "
            f"p95={_fmt_num(self.wfq_lag_quantile(0.95))} units/weight "
            f"(one engine-wide clock)"
        )
        if self.service_share_fn is not None:
            share = self.service_share_fn()
            if share:
                lines.append(
                    "service share: " + " ".join(
                        f"{lane}={frac:.0%}"
                        for lane, frac in sorted(share.items())
                    )
                )
        predicted = self.prefetch_hits + self.prefetch_misses
        if predicted:
            lines.append(
                f"predictive prefetch: hits={self.prefetch_hits} "
                f"misses={self.prefetch_misses} "
                f"hit_rate={self.prefetch_hits / predicted:.0%}"
            )
        lines.append(
            f"resilience: degraded_flushes={self.degraded_flushes} "
            f"flush_failures={self.flush_failures} "
            f"snapshots={self.snapshots} restores={self.restores}"
        )
        served = (
            self.shed_requests + self.expired_requests
            + self.timed_out_requests + self.reconnects + self.duplicate_hits
        )
        if served:
            lines.append(
                f"front door: shed={self.shed_requests} "
                f"expired={self.expired_requests} "
                f"timed_out={self.timed_out_requests} "
                f"reconnects={self.reconnects} "
                f"duplicate_hits={self.duplicate_hits}"
            )
        if self.security_budget_log2:
            worst = max(self.security_budget_log2.items(), key=lambda kv: kv[1])
            lines.append(
                f"security budget: {len(self.security_budget_log2)} tenants, "
                f"weakest log2 P_bf = {worst[1]:.3g} ({worst[0]})"
            )
        return "\n".join(lines)


@dataclasses.dataclass
class _Plan:
    """Device-side stacked secrets, patched in place as a registry churns."""

    version: int
    arrays: dict[str, jax.Array]    # name -> (S, ...) stacked per-slot secret
    # name -> per-slot device arrays, kept only for lanes named in
    # ``_sync_plan(..., keep_slots=)``.  The small-batch dispatch path and
    # the decode lane's row prefill index single slots on the host; slicing
    # the (S, ...) stack per call would copy, so those lanes pay 2x device
    # memory to keep the unstacked views resident.
    slots: dict[str, tuple] = dataclasses.field(default_factory=dict)


def _sync_plan(plan, registry, slot_fns: dict[str, Callable[[int], np.ndarray]],
               keep_slots: tuple[str, ...] = ()):
    """Bring a device plan up to ``registry.version``.

    ``slot_fns`` maps each stacked-array name to the registry's per-slot
    materializer.  Changed slots are patched with one scatter per stack —
    shapes are stable, so neither the scatter nor the jitted delivery steps
    retrace on tenant churn, and the (S, ...) stacks are copied once, not
    once per slot.  A full rebuild happens only when the changelog has been
    trimmed or capacity grew (auto-capacity doubling).

    Lanes named in ``keep_slots`` additionally retain the per-slot device
    arrays in ``plan.slots[name]`` (tuple of S arrays).  Patches build a new
    tuple rather than mutating, so earlier ``_WorkItem`` snapshots keep the
    secrets they were coalesced against.
    """
    if plan is not None and plan.version != registry.version:
        stable = all(
            a.shape[0] == registry.capacity for a in plan.arrays.values()
        )
        slots = registry.updates_since(plan.version) if stable else None
        if slots is None:
            plan = None         # capacity grew / changelog trimmed: rebuild
        elif not slots:  # pragma: no cover - version bump w/o slot churn
            plan = dataclasses.replace(plan, version=registry.version)
        else:
            idx = jnp.asarray(slots, jnp.int32)
            fresh = {
                name: {s: jnp.asarray(fn(s)) for s in slots}
                for name, fn in slot_fns.items() if name in keep_slots
            }
            plan = _Plan(
                version=registry.version,
                arrays={
                    name: plan.arrays[name].at[idx].set(
                        jnp.stack(list(fresh[name].values()))
                        if name in keep_slots
                        else np.stack([fn(s) for s in slots])
                    )
                    for name, fn in slot_fns.items()
                },
                slots={
                    name: tuple(
                        fresh[name].get(s, old)
                        for s, old in enumerate(plan.slots[name])
                    )
                    for name in plan.slots
                },
            )
    if plan is None:
        per_slot = {
            name: tuple(
                jnp.asarray(fn(s)) for s in range(registry.capacity)
            )
            for name, fn in slot_fns.items() if name in keep_slots
        }
        plan = _Plan(
            version=registry.version,
            arrays={
                name: jnp.stack(per_slot[name]) if name in keep_slots
                else jnp.asarray(
                    np.stack([fn(s) for s in range(registry.capacity)])
                )
                for name, fn in slot_fns.items()
            },
            slots=per_slot,
        )
    return plan


@dataclasses.dataclass
class _WorkItem:
    """One coalesced microbatch on its way through a phase-split flush.

    Each item carries its **own** plan snapshot: when capacity is smaller
    than the flushed tenant set, coalescing microbatch k+1 may evict-and-
    reuse slots that microbatch k's ``gidx`` still refers to — the snapshot
    taken right after each coalesce pins the slot contents that index
    vector was built against.  Snapshots are immutable jax arrays and alias
    the previous plan when nothing churned, so the steady state stores one
    plan G times, not G plans.
    """

    lane: str                   # "vision" | "tokens" | "features"
    mb: object                  # runtime.queue.Microbatch
    plan: _Plan                 # slot secrets as of this item's coalesce
    want_embed: bool = False    # tokens lane: run the Aug-Embedding gather
    out: object = None          # host results, set by execute_flush


@dataclasses.dataclass
class _ReqInfo:
    """Per-request scheduling trace, kept from admission to take_result."""

    request: DeliveryRequest        # normalized descriptor
    submitted_at: float             # time.monotonic() at enqueue
    queue_depth_at_submit: int      # engine-wide pending rows before enqueue
    coalesced_at: float | None = None   # set when its first rows coalesce
    completed_at: float | None = None   # set when a flush publishes the last row


@dataclasses.dataclass
class _FlushWork:
    """The coalesced work items one flush hands from phase to phase; holds
    everything execute_flush needs so it never touches mutable engine or
    registry state."""

    items: list


# Shape/static-arg tuples seen by actual traces of the jitted delivery steps.
# Python side effects inside a jitted function run only while tracing, so
# this counts compilations, not calls — the retrace-regression tests assert
# registration churn adds nothing here.
_TRACES: collections.Counter = collections.Counter()


def delivery_trace_count() -> int:
    """Total number of times the jitted delivery steps (vision rows, LM
    tokens) have been traced (process-wide)."""
    return sum(_TRACES.values())


class MoLeDeliveryEngine:
    """Multiplexes many tenants' delivery traffic over one compiled graph.

    A tenant is a **vision session** (``registry``: :class:`SessionRegistry`)
    or an **LM session** (``lm_registry``: :class:`LMSessionRegistry`); one
    engine can serve either kind or a mixed fleet.  Passing an
    ``LMSessionRegistry`` as the positional ``registry`` is accepted and
    routed to the LM lane, so single-kind callers need not know two names.

    **One typed front door.**  Every lane is addressed through
    :meth:`submit`/:meth:`deliver` with a
    :class:`repro.runtime.DeliveryRequest` (validated/normalized once in
    ``runtime.api``); results redeem as bare payloads (:meth:`take`) or full
    :class:`DeliveryResult` traces (:meth:`take_result`).  Scheduling is
    weighted fair queueing: registry weights set cross-tenant shares,
    ``DeliveryRequest.priority`` orders within a tenant, and
    ``DeliveryRequest.deadline_ms`` drives the async flusher.  (The legacy
    ``submit_tokens``/``submit_features``/``prepare_*``/``deliver_*`` shim
    trio was removed after a deprecation cycle; the typed request is the
    only spelling.)
    """

    def __init__(
        self,
        registry: SessionRegistry | LMSessionRegistry | None = None,
        *,
        lm_registry: LMSessionRegistry | None = None,
        max_rows: int = 64,
        row_buckets: tuple[int, ...] = (1, 2, 4, 8, 16, 32, 64),
        group_buckets: tuple[int, ...] = (1, 2, 4, 8, 16),
        seq_buckets: tuple[int, ...] = (8, 16, 32, 64, 128, 256, 512),
        backend: str | None = None,
        max_flush_microbatches: int = 64,
        injector=None,
        scheduler=None,
        decode_step_units: float = 1.0,
        clock: Callable[[], float] | None = None,
    ):
        from .queue import FairScheduler, RequestQueue, TokenQueue

        if isinstance(registry, LMSessionRegistry):
            if lm_registry is not None:
                raise ValueError(
                    "two LM registries given (positional + lm_registry=)"
                )
            registry, lm_registry = None, registry
        if registry is None and lm_registry is None:
            raise ValueError("need a vision registry, an LM registry, or both")
        self.registry = registry
        self.lm_registry = lm_registry
        self.backend = resolve_backend(backend)
        self.max_rows = max_rows
        # Bounds one flush round's working set: begin_flush coalesces at
        # most this many microbatches, so peak host memory (padded inputs +
        # materialized outputs held until publish) never scales with the
        # backlog — flush()/the async flusher simply run more rounds.
        self.max_flush_microbatches = int(max_flush_microbatches)
        self.row_buckets = tuple(sorted(row_buckets))
        self.group_buckets = tuple(sorted(group_buckets))
        self.seq_buckets = tuple(sorted(seq_buckets))
        # One id space across every lane: request ids key the shared result
        # table, so take() works the same whether the rid came from images,
        # tokens, or embedding rows.  A plain int (not itertools.count) so
        # snapshot()/restore() can serialize and rebuild the allocator.
        self._next_rid = 0

        def _alloc_rid() -> int:
            rid = self._next_rid
            self._next_rid += 1
            return rid

        self._id_alloc = _alloc_rid
        # ONE WFQ clock for the whole engine: every lane charges its service
        # units (rows; decode steps x decode_step_units when a decode lane
        # shares this scheduler) against the same per-tenant records, so a
        # tenant's weight is a true engine-wide share — splitting traffic
        # across vision + tokens + features (+ decode) buys nothing.
        # Weights resolve through the registries (weight_of), the single
        # source of truth; per-lane submit weights are not used.
        self.scheduler = (
            scheduler if scheduler is not None
            else FairScheduler(
                weight_of=self._weight_of, decode_step_units=decode_step_units
            )
        )
        # Injectable clock (seconds): the arrival predictor and prefetch
        # windows run on it, so tests/benchmarks drive synthetic time.
        self._clock = clock if clock is not None else time.monotonic
        self.predictor = ArrivalPredictor()
        # tenant -> prediction-window deadline (clock seconds): tenants
        # predictive_prefetch staged and is waiting to score.
        self._predicted: dict[str, float] = {}
        self.queue = (
            RequestQueue(
                registry.geom.in_features, max_rows=max_rows,
                row_buckets=self.row_buckets, group_buckets=self.group_buckets,
                id_alloc=self._id_alloc, scheduler=self.scheduler,
                service_lane="vision",
            )
            if registry is not None else None
        )
        self.token_queue = (
            TokenQueue(
                max_rows=max_rows, row_buckets=self.row_buckets,
                group_buckets=self.group_buckets, seq_buckets=self.seq_buckets,
                id_alloc=self._id_alloc, scheduler=self.scheduler,
            )
            if lm_registry is not None else None
        )
        self.embed_queue = (
            RequestQueue(
                lm_registry.d_in, max_rows=max_rows,
                row_buckets=self.row_buckets, group_buckets=self.group_buckets,
                id_alloc=self._id_alloc, scheduler=self.scheduler,
                service_lane="features",
            )
            if lm_registry is not None and lm_registry.has_embed_lane else None
        )
        self.stats = EngineStats()
        self.stats.service_share_fn = self.scheduler.service_share
        # Crash-safety hooks: the injector (resilience.FailureInjector)
        # raises SimulatedFailure at flush-phase boundaries; the straggler
        # monitor watches per-flush device time and flags degraded flushes
        # into EngineStats.degraded_flushes.
        self.injector = injector
        self.straggler = StragglerMonitor()
        self._plan: _Plan | None = None
        self._lm_plan: _Plan | None = None
        # The stacked (S, V, d_model) AugE tables are by far the largest
        # secrets; they are staged to the device lazily, only once a
        # deliver="embed" request has actually been seen — pure token-morph
        # traffic (serve.py --mode lm, the benchmark sweep) never pays the
        # upload or the device memory.
        self._embed_tables_needed = False
        self._results: dict[int, np.ndarray] = {}
        self._request_shape: dict[int, tuple[int, ...]] = {}
        self._token_deliver: dict[int, str] = {}   # rid -> "tokens" | "embed"
        self._embed_shape: dict[int, tuple[int, ...]] = {}
        self._req_info: dict[int, _ReqInfo] = {}
        self._done: set[int] = set()

    @property
    def pending_rows(self) -> int:
        """Unscheduled rows across every lane (rows == sequences for tokens)."""
        lanes = (self.queue, self.token_queue, self.embed_queue)
        return sum(q.pending_rows for q in lanes if q is not None)

    def _registry_of(self, tenant_id: str):
        """The registry holding ``tenant_id`` (vision first, then LM; None
        when unknown — the front door rejects such requests before here)."""
        if self.registry is not None and tenant_id in self.registry:
            return self.registry
        if self.lm_registry is not None and tenant_id in self.lm_registry:
            return self.lm_registry
        return None

    def _weight_of(self, tenant_id: str) -> float:
        """The scheduler's weight resolver: registry weights are the single
        source of truth for a tenant's engine-wide share, re-read on every
        submit so ``set_weight`` on a registry takes effect immediately."""
        reg = self._registry_of(tenant_id)
        return reg.weight_of(tenant_id) if reg is not None else 1.0

    # -- secrets ------------------------------------------------------------
    def prefetch(self, tenant_ids) -> dict[str, int]:
        """Activate tenants' slots and stage their secrets on device **now**,
        off the serving critical path (ROADMAP "slot prefetch").

        ``slot_for`` activates an evicted tenant lazily — but then the
        host->device copy of its secrets lands inside the next flush's
        coalesce phase.  Prefetching soon-to-be-active tenants moves that
        copy to whenever the caller has slack.  Tenants are looked up in the
        vision registry first, then the LM registry; activation order is the
        given order, so prefetching more tenants than a registry has slots
        keeps the **last** ``capacity`` of them resident (plain LRU).
        Returns {tenant_id: slot}.
        """
        slots: dict[str, int] = {}
        touched_vision = touched_lm = False
        for t in tenant_ids:
            if self.registry is not None and t in self.registry:
                slots[t] = self.registry.slot_for(t)
                touched_vision = True
            elif self.lm_registry is not None and t in self.lm_registry:
                slots[t] = self.lm_registry.slot_for(t)
                touched_lm = True
            else:
                raise KeyError(f"unknown tenant {t!r}")
        # Stage the patched slots to the device immediately: the next flush's
        # plan re-sync then finds version already current and copies nothing.
        if touched_vision:
            self._refresh_plan()
        if touched_lm:
            self._refresh_lm_plan()
        return slots

    def predictive_prefetch(self, horizon_ms: float = 50.0,
                            now: float | None = None) -> list[str]:
        """Stage evicted tenants the arrival predictor expects within
        ``horizon_ms`` (ROADMAP carry-over (a)): each front-door submission
        feeds the per-tenant EWMA/periodicity estimator, and this call —
        made whenever the caller has slack, e.g. the async flusher between
        rounds (``prefetch_horizon_ms``) — prefetches the due ones so their
        host->device secret upload happens *before* the burst instead of
        inside its first flush.  Predictions are scored on the tenant's next
        arrival: submitted-while-resident is a hit, window lapsed (or
        arrived evicted anyway) a miss — ``EngineStats.prefetch_hits`` /
        ``prefetch_misses`` gate whether the predictor earns its staging
        bandwidth.  Returns the tenants staged this call.
        """
        if now is None:
            now = self._clock()
        # Score prediction windows that lapsed without an arrival.
        for t, deadline in list(self._predicted.items()):
            if now > deadline:
                del self._predicted[t]
                self.stats.prefetch_misses += 1
        due: list[str] = []
        for t in self.predictor.due(horizon_ms / 1e3, now):
            if t in self._predicted:
                continue        # already staged, window still open
            reg = self._registry_of(t)
            if reg is None or reg.is_resident(t):
                continue        # unknown, or nothing to stage
            due.append(t)
        if due:
            self.prefetch(due)
            for t in due:
                iv = self.predictor.interval(t) or 0.0
                # The window closes one horizon + two intervals out: enough
                # slack that a slightly-late periodic tick still scores the
                # prefetch that actually served it.
                self._predicted[t] = now + horizon_ms / 1e3 + 2 * iv
        return due

    def _observe_arrival(self, tenant_id: str) -> None:
        """Feed the arrival predictor and score any open prediction."""
        now = self._clock()
        deadline = self._predicted.pop(tenant_id, None)
        if deadline is not None:
            reg = self._registry_of(tenant_id)
            if reg is not None and reg.is_resident(tenant_id) and now <= deadline:
                self.stats.prefetch_hits += 1
            else:
                self.stats.prefetch_misses += 1
        self.predictor.observe(tenant_id, now)

    def _refresh_plan(self) -> _Plan:
        reg = self.registry
        plan = _sync_plan(
            self._plan, reg,
            {"cores": reg.slot_core, "augs": reg.slot_aug},
            # The small-batch path indexes single slots on the host; only
            # the jnp backend routes there (Pallas shapes stay grouped).
            keep_slots=("cores", "augs") if self.backend == "jnp" else (),
        )
        if plan is not self._plan:
            self._plan = plan
            # Make the tenant count and the slot capacity group buckets: the
            # steady-state "every tenant active" microbatch of a capacity-
            # sized registry then lands exactly on G == tenant count (no
            # padding groups) and a fixed (G, B) bucket, minimizing both
            # padding and distinct compiled shapes.
            self.queue.ensure_group_bucket(len(reg))
            self.queue.ensure_group_bucket(reg.capacity)
        return plan

    def _refresh_lm_plan(self) -> _Plan:
        reg = self.lm_registry
        slot_fns = {"perms": reg.slot_perm}
        if self._embed_tables_needed:
            slot_fns["aug_embeds"] = reg.slot_aug_embedding
        keep = ()
        if reg.has_embed_lane:
            slot_fns["embed_cores"] = reg.slot_embed_core
            slot_fns["aug_projs"] = reg.slot_aug_projection
            if self.backend == "jnp":
                keep = ("embed_cores", "aug_projs")
        prev = self._lm_plan
        if prev is not None and set(prev.arrays) != set(slot_fns):
            prev = None   # lane set changed (first embed request): rebuild
        plan = _sync_plan(prev, reg, slot_fns, keep_slots=keep)
        if plan is not self._lm_plan:
            self._lm_plan = plan
            for q in (self.token_queue, self.embed_queue):
                if q is not None:
                    q.ensure_group_bucket(len(reg))
                    q.ensure_group_bucket(reg.capacity)
        return plan

    # -- request intake: the typed front door --------------------------------
    def submit(self, request: DeliveryRequest) -> int:
        """Enqueue one :class:`~repro.runtime.DeliveryRequest` (any lane).

        Returns a request id redeemable after :meth:`flush` via
        :meth:`take` / :meth:`take_result`.
        """
        return self._submit_request(request)

    def _submit_request(self, request: DeliveryRequest) -> int:
        return self._enqueue_normalized(api.normalize(request, self))

    def _enqueue_normalized(self, req: DeliveryRequest, *,
                            rid: int | None = None,
                            count_stats: bool = True) -> int:
        """Queue an already-:func:`api.normalize`-d request — the async front
        door normalizes outside its lock and calls this under it.

        ``rid`` pins the request id instead of allocating a fresh one —
        crash recovery (:meth:`restore` / :meth:`requeue_inflight`) replays
        in-flight requests under their original ids so waiters redeem the
        same handles; such replays pass ``count_stats=False`` so a request
        is counted once however many crashes it survives.
        """
        depth = self.pending_rows
        if count_stats:
            # Replays (count_stats=False) are re-deliveries, not arrivals:
            # feeding them to the predictor would corrupt the inter-arrival
            # history (and double-score prediction windows) after a crash.
            self._observe_arrival(req.tenant_id)
        # No per-submit weight: the shared scheduler resolves each tenant's
        # engine-wide share through the registries (weight_of) on every
        # lane() touch.
        if req.lane == "rows":
            g = self.registry.geom
            rid = self.queue.submit(
                req.tenant_id, req.payload, priority=req.priority, rid=rid
            )
            self._request_shape[rid] = (req.payload.shape[0], g.beta, g.n, g.n)
            n_rows = req.payload.shape[0]
        elif req.lane == "tokens":
            reg = self.lm_registry
            rid = self.token_queue.submit(
                req.tenant_id, req.payload, priority=req.priority, rid=rid
            )
            b, L = req.payload.shape
            if req.deliver == "embed":
                self._embed_tables_needed = True
            self._token_deliver[rid] = req.deliver
            self._request_shape[rid] = (
                (b, L) if req.deliver == "tokens" else (b, L, reg.d_model)
            )
            n_rows = b
        else:  # features
            reg = self.lm_registry
            rows = req.payload.reshape(-1, reg.d_in)
            rid = self.embed_queue.submit(
                req.tenant_id, rows, priority=req.priority, rid=rid
            )
            self._request_shape[rid] = (rows.shape[0], reg.d_out)
            self._embed_shape[rid] = req.payload.shape[:-1] + (reg.d_out,)
            n_rows = rows.shape[0]
        self._req_info[rid] = _ReqInfo(
            request=req, submitted_at=time.monotonic(),
            queue_depth_at_submit=depth,
        )
        if count_stats:
            self.stats.requests += 1
            self.stats.rows_in += n_rows
        return rid

    # -- the jitted hot paths ------------------------------------------------
    def _small_batch(self, gidx: np.ndarray, n_rows: int, plan: _Plan,
                     lane: str) -> bool:
        """Route tiny microbatches to the unrolled per-slot step.

        The grouped jnp reference is a scan of dynamic slices over the
        stacked secrets: on CPU that slice is a copy (~1.3 GB/s) while the
        GEMMs it feeds run at ~21 GB/s, so at B <= 8 the flush is
        copy-bound and *slower than per-request dispatch* (the b8/t16
        0.25x regression).  The unrolled step takes the per-slot device
        arrays as arguments instead — zero slicing — and wins there, but
        loses to the scan at B >= 16 (G dispatches of tiny GEMMs) and to
        the in-place batched einsum when ``gidx`` is the identity
        arrangement (the G == S steady state the fast case serves), so
        both keep the grouped path.
        """
        if self.backend != "jnp" or lane not in plan.slots or n_rows > 8:
            return False
        g, s = gidx.shape[0], len(plan.slots[lane])
        if g > 16:
            return False
        return not (g == s and np.array_equal(gidx, np.arange(s)))

    def _execute(self, x: np.ndarray, gidx: np.ndarray,
                 plan: _Plan) -> jax.Array:
        if self._small_batch(gidx, x.shape[1], plan, "cores"):
            return _delivery_step_small(
                jnp.asarray(x),
                tuple(plan.slots["cores"][g] for g in gidx),
                tuple(plan.slots["augs"][g] for g in gidx),
                self.registry.kappa,
            )
        return _delivery_step(
            jnp.asarray(x), jnp.asarray(gidx),
            plan.arrays["cores"], plan.arrays["augs"],
            self.registry.kappa, self.backend,
        )

    def _execute_tokens(self, tokens: np.ndarray, gidx: np.ndarray,
                        want_embed: bool, plan: _Plan):
        return _lm_delivery_step(
            jnp.asarray(tokens), jnp.asarray(gidx),
            plan.arrays["perms"],
            plan.arrays["aug_embeds"] if want_embed else None,
            self.backend, want_embed,
        )

    def _execute_features(self, x: np.ndarray, gidx: np.ndarray,
                          plan: _Plan) -> jax.Array:
        # The continuous LM lane *is* the vision math (m^2 -> 1): same jitted
        # step, with the registry's embedding cores / fused projections.
        if self._small_batch(gidx, x.shape[1], plan, "embed_cores"):
            return _delivery_step_small(
                jnp.asarray(x),
                tuple(plan.slots["embed_cores"][g] for g in gidx),
                tuple(plan.slots["aug_projs"][g] for g in gidx),
                self.lm_registry.kappa,
            )
        return _delivery_step(
            jnp.asarray(x), jnp.asarray(gidx),
            plan.arrays["embed_cores"], plan.arrays["aug_projs"],
            self.lm_registry.kappa, self.backend,
        )

    # -- phase-split flushing -------------------------------------------------
    def _note_microbatch(self, mb) -> None:
        self.stats.microbatches += 1
        self.stats.rows_padded += mb.n_padded_rows
        self.stats.bucket_shapes.add(mb.x.shape[:2])
        self.stats.padding_clamp_count += mb.n_clamped_padding
        # Queue wait, once per request: at the coalesce that takes its first
        # rows (a replayed request keeps its _ReqInfo, so it is not counted
        # again).
        now = time.monotonic()
        for s in mb.slices:
            info = self._req_info.get(s.request_id)
            if info is not None and info.coalesced_at is None:
                info.coalesced_at = now
                self.stats.record_queue_wait_ms(
                    (now - info.submitted_at) * 1e3
                )

    def _coalesce(self, vision_live: bool, lm_live: bool) -> _FlushWork | None:
        """Coalesce the live lanes' pending rows into work items (None when
        nothing is pending)."""
        work = _FlushWork(items=[])
        cap = self.max_flush_microbatches
        lanes: list[tuple[str, object, object, Callable[[], _Plan]]] = []
        if vision_live:
            self._refresh_plan()  # sync group buckets before coalescing
            lanes.append(
                ("vision", self.queue, self.registry, self._refresh_plan)
            )
        if lm_live:
            self._refresh_lm_plan()
            lanes.append(
                ("tokens", self.token_queue, self.lm_registry,
                 self._refresh_lm_plan)
            )
            if self.embed_queue is not None:
                lanes.append(
                    ("features", self.embed_queue, self.lm_registry,
                     self._refresh_lm_plan)
                )
        clamped = 0
        # WFQ lag sampled pre-coalesce: the spread the scheduler is about
        # to work off.  (Post-coalesce everything served is near-level.)
        # One sample per flush — the clock is engine-wide, not per-lane.
        self.stats.record_wfq_lag(self.scheduler.wfq_lag())
        # Round-robin the microbatch cap across the live lanes: one lane's
        # saturating backlog must not consume the whole round and starve the
        # others' deadlines (the async flusher's double-buffering refills
        # queues mid-flush, so a drained-in-fixed-order lane could otherwise
        # starve forever).  slot_for activates (and LRU-touches) each tenant
        # on lookup, so evicted tenants transparently regain a slot;
        # max_groups caps a microbatch at `capacity` distinct tenants so
        # activations within one coalesce round can never evict each other.
        # The plan re-sync after each coalesce pins the slots that
        # microbatch's gidx was built against (see _WorkItem).
        live = list(lanes)
        while live and len(work.items) < cap:
            for entry in list(live):
                if len(work.items) >= cap:
                    break
                lane, queue, reg, refresh = entry
                mb = queue.coalesce(reg.slot_for, max_groups=reg.capacity)
                if mb is None:
                    live.remove(entry)
                    continue
                self._note_microbatch(mb)
                clamped += mb.n_clamped_padding
                # One token microbatch may mix "tokens" and "embed"
                # requests; the Aug-Embedding gather runs only when someone
                # asked for features (a static flag — at most two traces
                # per bucket, independent of tenant churn).
                want_embed = lane == "tokens" and any(
                    self._token_deliver[s.request_id] == "embed"
                    for s in mb.slices
                )
                work.items.append(_WorkItem(lane, mb, refresh(), want_embed))
        if not work.items:
            return None
        if clamped:
            # Once per flush, not per microbatch: enough to make a sparse-
            # table layout regression observable without log spam.
            _log.warning(
                "coalesce clamped %d out-of-range padding slot indices this "
                "flush (total %d); see EngineStats.padding_clamp_count",
                clamped, self.stats.padding_clamp_count,
            )
        return work

    def begin_flush(self) -> _FlushWork | None:
        """Phase 1 (cheap, engine-state-mutating): coalesce pending rows
        into microbatch work items and snapshot the device plans.  The async
        front door runs this under its lock; the coalesced rows leave the
        queues, which immediately accept new submissions — the double-buffer
        that lets submitters progress mid-flush.  At most
        ``max_flush_microbatches`` items are taken per call so one round's
        working set stays bounded however deep the backlog; the caller loops
        until None, which is returned when nothing is pending.
        """
        vision_live = self.registry is not None and len(self.registry) > 0
        lm_live = self.lm_registry is not None and len(self.lm_registry) > 0
        if not vision_live and not lm_live:
            return None  # nothing registered yet -> nothing can be pending
        with self.stats.phase("coalesce") as timing:
            work = self._coalesce(vision_live, lm_live)
            if work is None:
                timing.keep = False   # nothing pending: not a flush
                return None
            self.stats.flushes += 1
        # The nastiest crash point: the coalesced rows have already left the
        # queues, so a failure here strands them unless recovery replays
        # from _req_info (requeue_inflight / restore).
        if self.injector is not None:
            self.injector.maybe_fail_phase("coalesce")
        return work

    def _dispatch(self, item: _WorkItem):
        """Start one work item's jitted step; returns its device output."""
        mb = item.mb
        if item.lane == "vision":
            return self._execute(mb.x, mb.group_tenant, item.plan)
        if item.lane == "tokens":
            return self._execute_tokens(
                mb.x, mb.group_tenant, item.want_embed, item.plan
            )
        return self._execute_features(mb.x, mb.group_tenant, item.plan)

    # analysis: forbids-lock(_cv)
    def execute_flush(self, work: _FlushWork) -> None:
        """Phase 2 (device compute, no engine-state mutation): run the jitted
        delivery steps over the work items' microbatches against the plan
        snapshots and materialize the results on host.

        Touches only ``work`` and immutable jax arrays, so the async flusher
        runs it **outside** its lock while submitters keep enqueuing.
        """
        if self.injector is not None:
            self.injector.maybe_fail_phase("device")
        with self.stats.phase("device") as timing:
            # Dispatch every step first (jax dispatch is async), then wait on
            # and fetch each in turn: the device pipelines the microbatches
            # instead of idling between them, and runs item i+1 while item
            # i's output is copied to the host.
            with span("mole.flush.dispatch"):
                outs = [self._dispatch(item) for item in work.items]
            for item, out in zip(work.items, outs):
                with span("mole.flush.wait"):
                    jax.block_until_ready(out)
                with span("mole.flush.fetch"):
                    if item.lane == "tokens":
                        morphed, feats = out
                        item.out = (
                            np.asarray(morphed),
                            None if feats is None else np.asarray(feats),
                        )
                    else:
                        item.out = np.asarray(out)
        dt_ms = timing.ms
        # Straggler watch: a device phase far above the running EMA flags
        # this flush as degraded (hung interconnect, preempted accelerator).
        if self.straggler.record(self.stats.flushes, dt_ms / 1e3):
            self.stats.degraded_flushes += 1
            _log.warning(
                "degraded flush #%d: device phase %.2fms vs EMA %.2fms",
                self.stats.flushes, dt_ms, self.straggler.ema * 1e3,
            )

    def publish_flush(self, work: _FlushWork) -> dict[int, np.ndarray]:
        """Phase 3 (cheap, engine-state-mutating): scatter executed results
        into per-request buffers and mark completed requests done.  Runs
        under the async front door's lock."""
        # Injected *before* any scatter: publish is all-or-nothing per
        # round, so recovery never sees a half-published flush.
        if self.injector is not None:
            self.injector.maybe_fail_phase("publish")
        done: dict[int, np.ndarray] = {}
        with self.stats.phase("publish"):
            for item in work.items:
                if item.lane == "vision":
                    self._publish_rows(item, done, self._finish_vision)
                elif item.lane == "tokens":
                    self._publish_tokens(item, done)
                else:
                    self._publish_rows(item, done, self._finish_features)
        return done

    def _mark_done(self, rid: int) -> None:
        """Stamp completion: the request's latency (with its priority) lands
        in the stats the moment its last row is published, sync and async
        alike."""
        self._done.add(rid)
        info = self._req_info.get(rid)
        if info is not None and info.completed_at is None:
            info.completed_at = time.monotonic()
            self.stats.record_latency_ms(
                (info.completed_at - info.submitted_at) * 1e3,
                priority=info.request.priority,
            )

    def _finish_vision(self, rid: int, buf: np.ndarray) -> np.ndarray:
        shape = self._request_shape[rid]
        return np.asarray(reroll_batch(buf, shape[1], shape[2]))

    def _finish_features(self, rid: int, buf: np.ndarray) -> np.ndarray:
        return buf.reshape(self._embed_shape[rid])

    def _publish_rows(self, item: _WorkItem, done: dict[int, np.ndarray],
                      finish) -> None:
        out = item.out
        for s in item.mb.slices:
            shape = self._request_shape[s.request_id]
            buf = self._results.setdefault(
                s.request_id,
                np.empty((shape[0], out.shape[-1]), np.float32),
            )
            buf[s.req_offset : s.req_offset + s.n_rows] = out[
                s.group, s.group_offset : s.group_offset + s.n_rows
            ]
            if s.req_offset + s.n_rows == shape[0]:
                done[s.request_id] = finish(s.request_id, buf)
                self._results[s.request_id] = done[s.request_id]
                self._mark_done(s.request_id)

    def _publish_tokens(self, item: _WorkItem,
                        done: dict[int, np.ndarray]) -> None:
        morphed, feats = item.out
        seq = item.mb.x.shape[2]     # this lane's padded sequence bucket
        for s in item.mb.slices:
            rid = s.request_id
            shape = self._request_shape[rid]   # (b, L) or (b, L, d)
            embed = self._token_deliver[rid] == "embed"
            buf = self._results.get(rid)
            if buf is None:
                buf = self._results[rid] = (
                    np.empty((shape[0], seq, feats.shape[-1]), np.float32)
                    if embed else np.empty((shape[0], seq), np.int32)
                )
            src = feats if embed else morphed
            buf[s.req_offset : s.req_offset + s.n_rows] = src[
                s.group, s.group_offset : s.group_offset + s.n_rows
            ]
            if s.req_offset + s.n_rows == shape[0]:
                # Strip the sequence padding back to the true length.
                done[rid] = np.ascontiguousarray(buf[:, : shape[1]])
                self._results[rid] = done[rid]
                self._mark_done(rid)

    def flush(self) -> dict[int, np.ndarray]:
        """Run every pending request (all lanes) through padded microbatches.

        Chains :meth:`begin_flush` -> :meth:`execute_flush` ->
        :meth:`publish_flush`, in rounds of at most
        ``max_flush_microbatches`` so memory stays bounded on deep backlogs.
        Returns {request_id: result} for all requests that completed during
        this flush (results are also retained until redeemed via
        :meth:`take`).  Vision requests resolve to features (b, beta, n, n);
        token requests to morphed tokens (b, L) or Aug-embedded features
        (b, L, d_model); continuous requests to projected features.
        """
        done: dict[int, np.ndarray] = {}
        while True:
            work = self.begin_flush()
            if work is None:
                return done
            self.execute_flush(work)
            done.update(self.publish_flush(work))

    def take_result(self, request_id: int) -> DeliveryResult:
        """Redeem a completed request as a :class:`DeliveryResult` (pops it):
        the delivered payload plus the per-request scheduling trace."""
        if request_id not in self._done:
            if request_id in self._request_shape:
                n_rows = self._request_shape[request_id][0]
                state = (
                    "partially delivered" if request_id in self._results
                    else "queued"
                )
                raise KeyError(
                    f"request {request_id} is still pending ({n_rows} rows, "
                    f"{state}; not yet completed by a flush) — call flush() "
                    f"before take()"
                )
            raise KeyError(
                f"unknown request id {request_id}: never submitted or already "
                f"taken ({len(self._done)} completed requests await take())"
            )
        out = self._results.pop(request_id)
        self._request_shape.pop(request_id, None)
        self._token_deliver.pop(request_id, None)
        self._embed_shape.pop(request_id, None)
        self._done.discard(request_id)
        info = self._req_info.pop(request_id)
        req = info.request
        return DeliveryResult(
            request_id=request_id, tenant_id=req.tenant_id, lane=req.lane,
            deliver=req.deliver, priority=req.priority, payload=out,
            submitted_at=info.submitted_at, completed_at=info.completed_at,
            queue_depth_at_submit=info.queue_depth_at_submit,
            metadata=req.metadata,
        )

    def take(self, request_id: int) -> np.ndarray:
        """Redeem a completed request's payload (pops it), any lane.

        :meth:`take_result` additionally returns the scheduling trace; this
        stays the payload-only spelling (it is not deprecated — the rid it
        redeems comes from ``submit(request)``).
        """
        return self.take_result(request_id).payload

    def deliver(self, request: DeliveryRequest) -> DeliveryResult:
        """Submit one request, flush, and return its :class:`DeliveryResult`."""
        rid = self._submit_request(request)
        self.flush()
        return self.take_result(rid)

    def reset_pending(self) -> None:
        """Drop every queued request and unredeemed result (failure reset).

        The async front door calls this after a failed flush: whatever is
        left in the queues / result buffers belongs to requests whose waiters
        have already been failed, and coalescing it later would only produce
        results nobody can take().  The shared id allocator survives, so
        request ids stay process-unique.
        """
        self._rebuild_queues()
        self._results.clear()
        self._request_shape.clear()
        self._token_deliver.clear()
        self._embed_shape.clear()
        self._req_info.clear()
        self._done.clear()

    def _rebuild_queues(self) -> None:
        """Replace every lane's queue with an empty twin (same buckets, same
        id allocator).  Crash recovery's first step: a queue abandoned mid-
        coalesce may have rows missing; rebuilding and replaying from
        ``_req_info`` is the only state the recovery paths trust."""
        from .queue import RequestQueue, TokenQueue

        if self.queue is not None:
            # release() hands the dead queue's backlog references back to
            # the shared scheduler — otherwise the engine-wide clock would
            # forever count the abandoned backlogs as live and stall.
            self.queue.release()
            self.queue = RequestQueue(
                self.queue.feature_dim, max_rows=self.max_rows,
                row_buckets=self.queue.row_buckets,
                group_buckets=self.queue.group_buckets,
                dtype=self.queue.dtype, id_alloc=self._id_alloc,
                scheduler=self.scheduler, service_lane="vision",
            )
        if self.token_queue is not None:
            tq = self.token_queue
            tq.release()
            self.token_queue = TokenQueue(
                max_rows=self.max_rows, row_buckets=tq.row_buckets,
                group_buckets=tq.group_buckets, seq_buckets=tq.seq_buckets,
                id_alloc=self._id_alloc, scheduler=self.scheduler,
            )
            # Carry the ensured group buckets over: the LM plan is still
            # current after a reset, so _refresh_lm_plan would not re-ensure
            # them — losing the tenant-count bucket would shift steady-state
            # microbatches onto a different (G, B) bucket and retrace.
            for g in sorted(tq._ensured_groups):
                self.token_queue.ensure_group_bucket(g)
        if self.embed_queue is not None:
            self.embed_queue.release()
            self.embed_queue = RequestQueue(
                self.embed_queue.feature_dim, max_rows=self.max_rows,
                row_buckets=self.embed_queue.row_buckets,
                group_buckets=self.embed_queue.group_buckets,
                dtype=self.embed_queue.dtype, id_alloc=self._id_alloc,
                scheduler=self.scheduler, service_lane="features",
            )

    # -- crash safety: snapshot / restore ------------------------------------
    def snapshot(self) -> EngineSnapshot:
        """Capture a crash-recovery image of the delivery plane.

        Arrays: every registry's per-tenant secrets (under ``vision/`` /
        ``lm/`` prefixes) plus, per un-taken request, either its normalized
        payload (``req/<rid>/payload``, still pending) or its finished
        result (``req/<rid>/result``).  Meta: slot bookkeeping + one
        JSON-able descriptor per request.  The queues themselves are **not**
        serialized: ``_req_info`` retains the full normalized payload of
        every in-flight request until take(), so :meth:`restore` simply
        replays the pending set under the original request ids — no lost
        and no duplicated ids, whatever phase the crash interrupted.
        """
        arrays: dict[str, np.ndarray] = {}
        meta: dict = {
            "next_rid": self._next_rid,
            "embed_tables_needed": self._embed_tables_needed,
            # The engine-wide fairness state (virtual clock + per-tenant
            # vtimes/weights + service counters): restoring it means a
            # tenant's banked debt survives a crash — without it every
            # tenant would re-enter at vtime 0 and heavy pre-crash users
            # would double-dip.
            "scheduler": self.scheduler.snapshot_state(),
            "registries": {},
            "requests": [],
        }
        for lane, reg in (("vision", self.registry), ("lm", self.lm_registry)):
            if reg is None:
                meta["registries"][lane] = None
                continue
            rmeta, rarrays = reg.snapshot_state()
            meta["registries"][lane] = rmeta
            for k, v in rarrays.items():
                arrays[f"{lane}/{k}"] = v
        for rid in sorted(self._req_info):
            info = self._req_info[rid]
            req = info.request
            md = req.metadata
            try:
                json.dumps(md)
            except TypeError:
                md = {}   # opaque caller annotations may not serialize
            done = rid in self._done
            meta["requests"].append({
                "rid": rid, "tenant": req.tenant_id, "lane": req.lane,
                "deliver": req.deliver, "priority": req.priority,
                "deadline_ms": req.deadline_ms, "metadata": md, "done": done,
                "submitted_at": info.submitted_at,
                "completed_at": info.completed_at,
                "queue_depth": info.queue_depth_at_submit,
            })
            if done:
                arrays[f"req/{rid:08d}/result"] = self._results[rid]
            else:
                arrays[f"req/{rid:08d}/payload"] = np.asarray(req.payload)
        self.stats.snapshots += 1
        # analysis: declassified(crash image: leaves the process only via the atomic CheckpointManager path)
        return EngineSnapshot(arrays=arrays, meta=meta)

    def restore(self, snap: EngineSnapshot) -> list[int]:
        """Rebuild this engine from a :meth:`snapshot` image and return the
        still-pending request ids (submission order).

        Works on a freshly constructed engine whose registries match the
        snapshot's kinds and geometry (validated by the registries), or in
        place over a live one.  The device plans are dropped and re-staged
        on the next flush; the restored stacks keep the same ``(S, ...)``
        shapes, so the process-global jit cache serves every delivery step —
        **zero retraces** across snapshot/restore.  Pending requests re-enter
        the queues under their original ids with their original scheduling
        traces; finished-but-untaken results are restored verbatim, so every
        submitted id is delivered exactly once.
        """
        meta, arrays = snap.meta, snap.arrays
        for lane, reg in (("vision", self.registry), ("lm", self.lm_registry)):
            rmeta = meta["registries"].get(lane)
            if (rmeta is None) != (reg is None):
                raise ValueError(
                    f"snapshot and engine disagree on the {lane} registry "
                    f"(snapshot {'has' if rmeta else 'lacks'} one)"
                )
            if reg is None:
                continue
            prefix = lane + "/"
            reg.restore_state(
                rmeta,
                {k[len(prefix):]: v for k, v in arrays.items()
                 if k.startswith(prefix)},
            )
        self._plan = None
        self._lm_plan = None
        self._embed_tables_needed = bool(meta["embed_tables_needed"])
        self.reset_pending()
        # After reset_pending the queues are drained (no backlog refs), so
        # the scheduler state can be swapped wholesale; the replay below
        # re-enters each pending tenant's backlog through submit, and since
        # every restored vtime satisfies vtime >= vnow, the idle re-entry
        # max() is a no-op — fairness positions round-trip exactly.
        if meta.get("scheduler") is not None:
            self.scheduler.restore_state(meta["scheduler"])
        pending: list[int] = []
        for desc in meta["requests"]:
            rid = int(desc["rid"])
            md = desc.get("metadata") or {}
            if desc["done"]:
                self._results[rid] = arrays[f"req/{rid:08d}/result"]
                self._done.add(rid)
                self._req_info[rid] = _ReqInfo(
                    request=DeliveryRequest(
                        desc["tenant"], None, lane=desc["lane"],
                        deliver=desc["deliver"],
                        priority=int(desc["priority"]),
                        deadline_ms=desc["deadline_ms"], metadata=md,
                    ),
                    submitted_at=desc["submitted_at"],
                    queue_depth_at_submit=int(desc["queue_depth"]),
                    completed_at=desc["completed_at"],
                )
            else:
                req = DeliveryRequest(
                    desc["tenant"], arrays[f"req/{rid:08d}/payload"],
                    lane=desc["lane"], deliver=desc["deliver"],
                    priority=int(desc["priority"]),
                    deadline_ms=desc["deadline_ms"], metadata=md,
                )
                self._enqueue_normalized(req, rid=rid, count_stats=False)
                info = self._req_info[rid]
                info.submitted_at = desc["submitted_at"]
                info.queue_depth_at_submit = int(desc["queue_depth"])
                pending.append(rid)
        self._next_rid = max(self._next_rid, int(meta["next_rid"]))
        self.stats.restores += 1
        return pending

    def requeue_inflight(self) -> list[int]:
        """In-process crash recovery: rebuild the (possibly half-coalesced)
        queues and replay every not-yet-done request under its original id.

        The async front door calls this when a flush round dies between
        phases: the coalesced work items are lost with the round, but
        ``_req_info`` still holds every in-flight request's normalized
        payload — re-enqueuing those (and dropping any partially filled
        result buffers) makes the next round deliver each exactly once.
        Finished-but-untaken results are untouched.  Returns the replayed
        ids in submission order.
        """
        self._rebuild_queues()
        pending = sorted(set(self._req_info) - self._done)
        for rid in pending:
            self._results.pop(rid, None)   # drop partial row buffers
            info = self._req_info[rid]
            self._enqueue_normalized(
                info.request, rid=rid, count_stats=False
            )
            self._req_info[rid] = info     # keep the original trace
        return pending


# analysis: forbids-lock(_cv)
@partial(jax.jit, static_argnames=("kappa", "backend"))
def _delivery_step(x, gidx, cores, augs, kappa: int, backend: str):
    """morph + Aug forward for one padded microbatch, single compiled graph.

    x: (G, B, F_in); gidx: (G,); cores: (S, q, q); augs: (S, F_in, F_out).
    Serves both the vision rows lane (Aug-Conv) and the continuous LM lane
    (fused input projections) — the same math, per the paper's m^2 -> 1
    reduction.  The group axis is the natural data-parallel shard axis
    (delivery_rules).

    One path for every ``gidx``: the grouped kernels read each group's
    secrets in place from the stacked slot arrays (scalar-prefetched index
    maps on Pallas, a scan of dynamic slices on jnp), so there is no
    ``secrets[gidx]`` copy and no identity-order special case to fall off.
    """
    _TRACES[(x.shape, gidx.shape, cores.shape, kappa, backend)] += 1
    x = hint(x, "dp")
    morphed = morph_rows_grouped(x, gidx, cores, kappa, backend=backend)
    morphed = hint(morphed, "dp")
    feats = aug_conv_forward_grouped(morphed, gidx, augs, backend=backend)
    return hint(feats, "dp")


# analysis: forbids-lock(_cv)
@partial(jax.jit, static_argnames=("backend", "want_embed"))
def _lm_delivery_step(tokens, gidx, perms, aug_embeds, backend: str,
                      want_embed: bool):
    """Token morph (+ optional Aug-Embedding) for one padded microbatch.

    tokens: (G, B, L) int32; gidx: (G,); perms: (S, V) int32;
    aug_embeds: (S, V, d), or None when ``want_embed`` is False (the engine
    stages the AugE stacks lazily).  Returns (morphed, feats) where feats is
    None unless ``want_embed`` — the provider-side permutation gather always
    runs (it is what crosses the trust boundary), the developer-side AugE
    gather only when a request asked for delivered features.  Like the rows
    step, the grouped gathers read the stacked tables in place for any
    ``gidx`` — no per-microbatch ``perms[gidx]`` / ``aug_embeds[gidx]`` copy.
    """
    _TRACES[
        ("lm", tokens.shape, gidx.shape, perms.shape, backend, want_embed)
    ] += 1
    tokens = hint(tokens, "dp")
    morphed = token_morph_grouped(tokens, gidx, perms, backend=backend)
    morphed = hint(morphed, "dp")
    if not want_embed:
        return morphed, None
    feats = aug_embed_grouped(morphed, gidx, aug_embeds, backend=backend)
    return morphed, hint(feats, "dp")


# analysis: forbids-lock(_cv)
@partial(jax.jit, static_argnames=("kappa",))
def _delivery_step_small(x, cores: tuple, augs: tuple, kappa: int):
    """Small-batch sibling of :func:`_delivery_step`: per-group secrets as
    separate arguments, groups unrolled.

    x: (G, B, F_in); cores / augs: G-tuples of (q, q) / (F_in, F_out) —
    the per-slot device arrays :func:`_sync_plan` keeps alongside the
    stacks.  Same per-group reference math as the scan path (bit-identical
    output); what changes is only how each group's secrets reach it: as
    pre-sliced arguments, not ``dynamic_slice`` copies out of the stack.
    Retraces per distinct (shape, G, kappa) — G is bucketized and routing
    caps it at 16, so the trace set stays small.
    """
    _TRACES[("small", x.shape, len(cores), kappa)] += 1
    x = hint(x, "dp")
    outs = []
    for g in range(x.shape[0]):
        t = kref.block_diag_matmul_ref(x[g], cores[g], kappa)
        outs.append(kref.aug_gemm_ref(t, augs[g]))
    return hint(jnp.stack(outs), "dp")
