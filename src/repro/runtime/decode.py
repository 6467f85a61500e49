"""Continuous-batched cross-tenant LM decode on top of the delivery plane.

One shared batched decode step runs over a fixed pool of **rows**:

  * Row ``r`` holds one tenant *sequence* — its morphed token, its absolute
    position, its KV cache row (``launch.steps.row_caches``), and the
    registry slot ``sidx[r]`` whose stacked AugE table / Aug-head serve its
    embedding and logits.  Rows of one tenant share its slot, so the slot
    capacity may be smaller than the row count: a slot is the tenant's, not
    the sequence's.
  * **Continuous batching**: between steps, finished sequences retire and
    queued ones are admitted under weighted fair queueing
    (:class:`repro.runtime.queue.FairAdmissionQueue`).  Admission is
    slot-aware: a sequence whose tenant holds no slot waits until some slot
    has no active row (the registry pins a tenant while it has rows, so its
    slot is never evicted mid-decode), and while the least-served tenant
    waits so does everyone, so the pinned slots drain and it cannot starve.
    A joiner prefills into a free row's cache and decoding resumes with the
    *same* compiled step: every array argument keeps its shape, so the
    jitted step never retraces on churn.
  * Prompts are right-padded to ``prompt_buckets`` (one prefill compile per
    bucket).
  * Inactive rows decode garbage against their stale state; their outputs
    are ignored on the host, and they route to no expert and read no
    Aug-head (the step takes the live rows' mask).  Rows are independent
    (per-row attention and positions, per-row grouped gathers, and an FFN
    that maps each token on its own: the MoE is dropless, so no row's
    routing displaces another's).

A step is three phases, the shape of the delivery engine's flush
(``runtime.engine``): :meth:`ContinuousDecodeLane.begin_step` (admission and
slot upkeep, host state only), :meth:`~ContinuousDecodeLane.run_step` (the
prefills and the step on the device) and
:meth:`~ContinuousDecodeLane.finish_step` (the tokens onto their rows,
finished sequences retired); :meth:`~ContinuousDecodeLane.step` chains them.

Secrets reach the step through the engine's ``_sync_plan`` machinery:
stacked ``(S, V, d)`` AugE tables and ``(S, d, V)`` Aug-heads (in the
model's parameter dtype), patched in place on tenant churn.  Prefills read the same
stacks through a traced slot index, so no per-slot copy exists.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.lm import LMSessionRegistry

from .engine import _TRACES, _Plan, _sync_plan
from .queue import FairAdmissionQueue, FairScheduler, bucketize
from .resilience import EngineSnapshot
from .tracing import span

__all__ = ["ContinuousDecodeLane", "DecodeRow", "DecodeWork"]


@dataclasses.dataclass
class DecodeRow:
    """Host-side bookkeeping for one active lane row."""

    seq_id: int
    tenant_id: str
    slot: int
    remaining: int                 # tokens still owed
    generated: list = dataclasses.field(default_factory=list)  # morphed ids
    logits: list = dataclasses.field(default_factory=list)     # of each id
    # Admission-time descriptor, retained for crash recovery: restore()
    # replays the sequence from scratch (greedy decode is deterministic,
    # so the regenerated tokens are identical).
    prompt: np.ndarray | None = None   # morphed prompt as admitted
    max_new_tokens: int = 0
    priority: int = 0


@dataclasses.dataclass
class DecodeWork:
    """One step on its way through the three phases.  ``record`` is filled
    as the step runs: rows decoded, their positions and slots, each
    prefill's prompt length, bucket and experts hit, and the experts each
    MoE layer's routing hit in the step."""

    joins: list                    # (row, slot, morphed prompt)
    admitted: list                 # the joiners' seq_ids
    plan: _Plan
    sidx: np.ndarray
    tokens: np.ndarray
    t: np.ndarray
    live: np.ndarray               # rows decoded this step
    record: dict = dataclasses.field(default_factory=dict)
    out: tuple | None = None


class ContinuousDecodeLane:
    """A fixed pool of decode rows multiplexing many tenants' generations.

    Parameters
    ----------
    model, params:
        The shared trunk (tenant-independent weights), stacked as
        ``Model.init`` gives them or already a layer at a time
        (``launch.steps.layer_params``), the form the lane keeps.  Per-tenant
        embedding/head artifacts come from ``registry``, never from
        ``params`` — the trust boundary of the delivery engine.
    registry:
        :class:`LMSessionRegistry` holding every tenant's secrets.  Any slot
        capacity works: rows of one tenant share its slot.
    rows:
        Decode batch width R.  Fixed for the lane's lifetime (that is what
        makes the step shape-stable).
    max_len:
        KV capacity per row (prompt bucket and prompt + generated tokens
        must fit).
    backend:
        Kernel backend for the grouped kernels (None = auto).
    prompt_buckets:
        Prefill lengths prompts are right-padded to (None: the powers of
        two from 8 below ``max_len``, and ``max_len``).

    The device stacks of AugE tables and Aug-heads take the model's
    parameter dtype.
    """

    def __init__(
        self,
        model,
        params,
        registry: LMSessionRegistry,
        *,
        rows: int = 16,
        max_len: int,
        backend: str | None = None,
        injector=None,
        scheduler=None,
        prompt_buckets: tuple[int, ...] | None = None,
    ):
        # The step builders live in launch.steps with the other serving
        # steps; importing lazily keeps runtime importable without the
        # launch layer (and avoids the upside-down import at module scope).
        from repro.launch.steps import (
            layer_caches, layer_params, make_batched_decode_step,
            make_row_prefill_step, row_caches, scatter_row,
        )

        self.model = model
        # A layer at a time: the steps read each layer's arrays whole.
        self.params = (params if "layers" in params
                       else layer_params(params, model.cfg))
        self.registry = registry
        self.rows = int(rows)
        self.max_len = int(max_len)
        if prompt_buckets is None:
            prompt_buckets = [b for b in (2 ** i for i in range(3, 32))
                              if b < self.max_len] + [self.max_len]
        self.prompt_buckets = tuple(sorted(prompt_buckets))
        if self.prompt_buckets[-1] > self.max_len:
            raise ValueError(
                f"prompt bucket {self.prompt_buckets[-1]} exceeds max_len "
                f"{self.max_len}"
            )
        # Admission charges the scheduler max_new_tokens x decode_step_units
        # per taken sequence.  Pass the delivery engine's scheduler
        # (``scheduler=engine.scheduler``) to make decode appetite count
        # against the same engine-wide per-tenant shares as the morph lanes;
        # a stand-alone lane gets a private clock with weights resolved
        # through this registry.
        if scheduler is None:
            scheduler = FairScheduler(weight_of=registry.weight_of)
        self.queue = FairAdmissionQueue(scheduler)
        self._plan: _Plan | None = None
        self._results: dict[int, tuple[np.ndarray, np.ndarray]] = {}
        self._waited: set[int] = set()      # queued ones that waited for a slot
        self.slot_waits = 0                 # sequences that ever waited
        # Crash-safety hook: raises SimulatedFailure at the "retire"/"admit"
        # boundaries of step() (tests / serve.py --inject-failure).
        self.injector = injector

        decode_fn = make_batched_decode_step(model, backend=backend)
        prefill_fn = make_row_prefill_step(model, backend=backend)

        def counted_decode(params_, aug_embeds, aug_heads, sidx, tokens, t,
                           live, caches):
            _TRACES[
                ("decode_lane", tokens.shape, aug_embeds.shape,
                 aug_heads.shape)
            ] += 1
            return decode_fn(params_, aug_embeds, aug_heads, sidx, tokens, t,
                             live, caches)

        def counted_prefill(params_, aug_embeds, aug_heads, slot, tokens,
                            length):
            _TRACES[("decode_lane_prefill", tokens.shape)] += 1
            return prefill_fn(params_, aug_embeds, aug_heads, slot, tokens,
                              length, layer_caches(model, 1, self.max_len))

        def join(caches, one, tokens, first, row):
            return scatter_row(caches, one, row), tokens.at[row].set(first[0])

        self._decode = jax.jit(counted_decode, donate_argnums=(7,))
        # The fresh B=1 cache is made inside; one trace per prompt bucket.
        self._prefill = jax.jit(counted_prefill)
        # A joiner's cache into its row and its first token into the step's
        # tokens, on the device; traced row index: one compile serves every
        # row.
        self._join = jax.jit(join, donate_argnums=(0,))
        self._row_caches = lambda: row_caches(model, self.rows, self.max_len)
        self._reset_rows()

    def _reset_rows(self) -> None:
        # Fresh rows are all-empty, so even before any admission the decode
        # step computes harmlessly on garbage.
        self._caches = self._row_caches()
        self._row: list[DecodeRow | None] = [None] * self.rows
        self._sidx = np.zeros(self.rows, np.int32)
        self._tokens = np.zeros(self.rows, np.int32)
        self._t = np.zeros(self.rows, np.int32)
        self.registry.unpin()

    # -- submission ----------------------------------------------------------
    @property
    def active(self) -> int:
        return sum(r is not None for r in self._row)

    @property
    def busy(self) -> bool:
        """Whether a sequence is queued or decoding."""
        return bool(len(self.queue) or self.active)

    def prompt_len(self, n: int) -> int:
        """The prefill length of an ``n``-token prompt."""
        return bucketize(n, self.prompt_buckets)

    def check(self, n_prompt: int, max_new_tokens: int) -> None:
        """Refuse a generation the lane cannot hold (ValueError)."""
        if n_prompt == 0:
            raise ValueError("empty prompt")
        if n_prompt > self.prompt_buckets[-1]:
            raise ValueError(
                f"prompt of {n_prompt} tokens exceeds the largest prompt "
                f"bucket {self.prompt_buckets[-1]}"
            )
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if max(self.prompt_len(n_prompt), n_prompt + max_new_tokens) > self.max_len:
            raise ValueError(
                f"prompt ({n_prompt}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_len ({self.max_len})"
            )

    def submit(self, tenant_id: str, prompt, max_new_tokens: int, *,
               priority: int = 0, premorphed: bool = False) -> int:
        """Queue one generation request; returns a ``seq_id`` for take().

        ``prompt`` is a (L,) / (1, L) int sequence.  The provider-side vocab
        morph (the tenant's permutation, host state) is applied here unless
        the caller already routed the prompt through the engine's token lane
        (``premorphed=True`` — serve.py's ``--mode lm``).
        """
        sess = self.registry.session(tenant_id)
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.check(prompt.size, max_new_tokens)
        if not premorphed:
            prompt = sess.morpher.perm[prompt].astype(np.int32)
        # No per-submit weight: the scheduler resolves the tenant's share
        # through its weight_of resolver (this registry, or the whole
        # engine's resolver when the scheduler is shared).
        return self.queue.submit(
            tenant_id, prompt, max_new_tokens, priority=priority
        )

    # -- plan upkeep ---------------------------------------------------------
    def _table(self, fn):
        dtype = self.model.cfg.pdtype
        return lambda slot: fn(slot).astype(dtype)

    def _refresh_plan(self) -> _Plan:
        reg = self.registry
        self._plan = _sync_plan(
            self._plan, reg,
            {"aug_embeds": self._table(reg.slot_aug_embedding),
             "aug_heads": self._table(reg.slot_aug_head)},
        )
        return self._plan

    def _check_slots(self) -> None:
        """No active row's slot may have changed hands (pinning holds them)."""
        for r in self._row:
            if r is not None and self.registry._slot_tenant[r.slot] != r.tenant_id:
                raise RuntimeError(
                    f"tenant {r.tenant_id!r} lost slot {r.slot} mid-decode"
                )

    # -- the three phases ------------------------------------------------------
    def _admissible(self, tenant_id: str) -> bool:
        """A tenant with a slot can always join; one without needs a slot
        that no active row holds (a free one, or one LRU eviction may take)."""
        reg = self.registry
        if reg.is_resident(tenant_id):
            return True
        held = {r.slot for r in self._row if r is not None}
        return len(held) < reg.capacity

    def _admit(self, hold: bool) -> list:
        joins = []
        free = [i for i, r in enumerate(self._row) if r is None]
        while free and len(self.queue) and not hold:
            item = self.queue.take(admissible=self._admissible)
            if item is None:
                # Every queued tenant needs a slot that active rows hold.
                fresh = set(self.queue.waiting_ids()) - self._waited
                self.slot_waits += len(fresh)
                self._waited |= fresh
                break
            row = free.pop(0)
            self._waited.discard(item.seq_id)
            slot = self.registry.slot_for(item.tenant_id)
            self.registry.pin(item.tenant_id)
            self._row[row] = DecodeRow(
                seq_id=item.seq_id, tenant_id=item.tenant_id, slot=slot,
                remaining=item.max_new_tokens, prompt=item.prompt,
                max_new_tokens=item.max_new_tokens, priority=item.priority,
            )
            self._sidx[row] = slot
            self._t[row] = item.prompt.size
            joins.append((row, slot, item.prompt))
        return joins

    def begin_step(self, hold: bool = False) -> DecodeWork | None:
        """Phase 1 (host state): admit queued sequences into free rows (none
        with ``hold``: a tenant elsewhere waits for a slot these rows pin)
        and bring the slot stacks up to date.  None when no row is active."""
        if self.injector is not None:
            self.injector.maybe_fail_phase("admit")
        joins = self._admit(hold)
        if self.active == 0:
            return None
        self._check_slots()
        return DecodeWork(
            joins=joins, admitted=[self._row[r].seq_id for r, _, _ in joins],
            plan=self._refresh_plan(), sidx=self._sidx.copy(),
            tokens=self._tokens.copy(), t=self._t.copy(),
            live=np.array([r is not None for r in self._row]),
        )

    # analysis: forbids-lock(_cv)
    def run_step(self, work: DecodeWork) -> None:
        """Phase 2 (device): prefill the joiners into their rows, then one
        decode step over every row; fetch the tokens, their logits and the
        routing counts into ``work.out`` and ``work.record``.  A joiner's
        first token goes into the step's tokens on the device and is
        fetched with the step's outputs, so no prefill waits on the host.
        The step's call, wait and copy open the flush's
        ``mole.flush.dispatch``, ``mole.flush.wait`` and
        ``mole.flush.fetch`` spans (the wait also covers the prefills)."""
        embeds, heads = work.plan.arrays["aug_embeds"], work.plan.arrays["aug_heads"]
        tokens = jnp.asarray(work.tokens)
        firsts, sizes = [], []
        for row, slot, prompt in work.joins:
            n = prompt.size
            padded = np.zeros((1, self.prompt_len(n)), np.int32)
            padded[0, :n] = prompt
            with span("mole.decode.prefill"):
                tok, logit, hits, one = self._prefill(
                    self.params, embeds, heads, jnp.asarray(slot, jnp.int32),
                    jnp.asarray(padded), jnp.asarray(n, jnp.int32),
                )
                self._caches, tokens = self._join(
                    self._caches, one, tokens, tok, jnp.asarray(row, jnp.int32))
            firsts.append((tok, logit, hits))
            sizes.append((n, padded.shape[1]))
        with span("mole.decode.step"):
            # The flush's phases inside the step: the call, the wait for
            # the device, the copy of tokens, logits and routing counts.
            with span("mole.flush.dispatch"):
                nxt, logits, hits, self._caches = self._decode(
                    self.params, embeds, heads, jnp.asarray(work.sidx),
                    tokens, jnp.asarray(work.t),
                    jnp.asarray(work.live), self._caches,
                )
            with span("mole.flush.wait"):
                jax.block_until_ready((nxt, logits, hits))
            with span("mole.flush.fetch"):
                (nxt, logits, hits), firsts = jax.device_get(
                    ((nxt, logits, hits), firsts))
        work.out = ([(int(t[0]), float(lg[0])) for t, lg, _ in firsts],
                    nxt, logits)
        prefills = [(n, b, np.asarray(h).tolist())
                    for (n, b), (_, _, h) in zip(sizes, firsts)]
        work.record.update(rows=int(work.live.sum()), prefills=prefills,
                           hits=np.asarray(hits).tolist(),
                           positions=work.t[work.live].tolist(),
                           slots=sorted(set(work.sidx[work.live].tolist())))

    def finish_step(self, work: DecodeWork) -> list[int]:
        """Phase 3 (host state): put the step's tokens on their rows and
        retire the finished sequences; returns their seq_ids, redeemable
        with :meth:`take` / :meth:`take_result`."""
        firsts, nxt, logits = work.out
        for (row, _, _), (tok, logit) in zip(work.joins, firsts):
            r = self._row[row]
            r.generated.append(tok)
            r.logits.append(logit)
            r.remaining -= 1
            self._tokens[row] = tok
        for i, r in enumerate(self._row):
            if r is None or not work.live[i] or r.remaining == 0:
                continue
            r.generated.append(int(nxt[i]))
            r.logits.append(float(logits[i]))
            r.remaining -= 1
            self._tokens[i] = nxt[i]
            self._t[i] += 1
        return self._retire()

    def _retire(self) -> list[int]:
        done = []
        for i, r in enumerate(self._row):
            if r is not None and r.remaining == 0:
                inv = self.registry.session(r.tenant_id).morpher.inv_perm
                self._results[r.seq_id] = (
                    inv[np.asarray(r.generated, np.int64)].astype(np.int32),
                    np.asarray(r.logits, np.float32),
                )
                self._row[i] = None
                done.append(r.seq_id)
                if not any(o is not None and o.tenant_id == r.tenant_id
                           for o in self._row):
                    self.registry.unpin(r.tenant_id)
        return done

    def step(self) -> int:
        """Admit, run one batched decode step, retire.  Returns the number
        of rows still active."""
        if self.injector is not None:
            self.injector.maybe_fail_phase("retire")
        work = self.begin_step()
        if work is None:
            return 0
        self.run_step(work)
        self.finish_step(work)
        return self.active

    def run(self) -> None:
        """Drive steps until every queued/active sequence has finished."""
        while self.busy:
            self.step()

    def take_result(self, seq_id: int) -> tuple[np.ndarray, np.ndarray]:
        """Redeem a finished sequence: its unmorphed generated tokens and the
        logit each was chosen with."""
        if seq_id not in self._results:
            raise KeyError(
                f"sequence {seq_id} not finished (or already taken)"
            )
        return self._results.pop(seq_id)

    def take(self, seq_id: int) -> np.ndarray:
        """Redeem a finished sequence's unmorphed generated tokens."""
        return self.take_result(seq_id)[0]

    def reset(self) -> None:
        """Drop every queued and active sequence (failure recovery: the
        engine replays their requests from the start)."""
        self.queue.release()
        self.queue = FairAdmissionQueue(self.queue.scheduler)
        self._waited.clear()
        self._reset_rows()

    # -- crash safety: snapshot / restore ------------------------------------
    def snapshot(self) -> EngineSnapshot:
        """Capture a crash-recovery image of the lane.

        Registry secrets (under ``lm/``), every unfinished sequence's
        admitted (morphed) prompt + descriptor — active rows and queued
        alike — and every finished-but-untaken result.  KV caches are **not**
        serialized: greedy decode is deterministic, so :meth:`restore`
        replays unfinished sequences from scratch and regenerates identical
        tokens at a fraction of the snapshot size.
        """
        arrays: dict[str, np.ndarray] = {}
        rmeta, rarrays = self.registry.snapshot_state()
        for k, v in rarrays.items():
            arrays[f"lm/{k}"] = v
        meta: dict = {
            "registry": rmeta,
            "next_sid": self.queue._next_id,
            # Fairness positions (virtual clock + per-tenant vtimes) survive
            # a crash with the sequences.  With an engine-shared scheduler
            # the engine's snapshot carries the same state; restoring either
            # image yields the same scheduler positions.
            "scheduler": self.queue.scheduler.snapshot_state(),
            "sequences": [],
            "finished": sorted(self._results),
        }
        live = [r for r in self._row if r is not None]
        for entry in live + self.queue.snapshot_items():
            sid = int(entry.seq_id)
            meta["sequences"].append({
                "sid": sid, "tenant": entry.tenant_id,
                "max_new_tokens": int(entry.max_new_tokens),
                "priority": int(entry.priority),
            })
            arrays[f"seq/{sid:08d}/prompt"] = np.asarray(entry.prompt)
        for sid in meta["finished"]:
            arrays[f"res/{sid:08d}/tokens"] = self._results[sid][0]
            arrays[f"res/{sid:08d}/logits"] = self._results[sid][1]
        # analysis: declassified(crash image: leaves the process only via the atomic CheckpointManager path)
        return EngineSnapshot(arrays=arrays, meta=meta)

    def restore(self, snap: EngineSnapshot) -> list[int]:
        """Rebuild the lane from a :meth:`snapshot` image; returns the
        unfinished seq_ids that were re-queued (admission order).

        Every unfinished sequence — whether it was mid-decode or still
        queued at snapshot time — re-enters the admission queue under its
        original seq_id with its original (already morphed) prompt; the
        next :meth:`run` regenerates it deterministically.  Row pool,
        stacked caches, and position state are reset to empty; the stacks
        keep their shapes, so nothing retraces.
        """
        meta, arrays = snap.meta, snap.arrays
        self.registry.restore_state(
            meta["registry"],
            {k[3:]: v for k, v in arrays.items() if k.startswith("lm/")},
        )
        self._plan = None
        self.reset()
        if meta.get("scheduler") is not None:
            # Queues are drained here, so the fairness state swaps wholesale;
            # the replay below re-enters each backlog, and restored vtimes
            # satisfy vtime >= vnow so re-entry keeps them exactly.
            self.queue.scheduler.restore_state(meta["scheduler"])
        self._results = {}
        pending: list[int] = []
        for desc in meta["sequences"]:
            sid = int(desc["sid"])
            # Straight into the raw queue: the stored prompt is already
            # morphed, so going through submit() would double-morph it.
            self.queue.submit(
                desc["tenant"], arrays[f"seq/{sid:08d}/prompt"],
                int(desc["max_new_tokens"]), priority=int(desc["priority"]),
                sid=sid,
            )
            pending.append(sid)
        for sid in meta["finished"]:
            sid = int(sid)
            self._results[sid] = (arrays[f"res/{sid:08d}/tokens"],
                                  arrays[f"res/{sid:08d}/logits"])
        self.queue._next_id = max(self.queue._next_id, int(meta["next_sid"]))
        return pending
