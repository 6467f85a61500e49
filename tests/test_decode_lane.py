"""Continuous-batched cross-tenant decode (repro.runtime.decode): batched
decode bit-matches the per-tenant loop for every tenant after unmorphing,
mid-stream join/leave never retraces the jitted step, and admission follows
weighted fair queueing."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.core.lm import LMSessionRegistry
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.models.api import Model
from repro.runtime import (
    ContinuousDecodeLane, FailureInjector, FairAdmissionQueue, SimulatedFailure,
    delivery_trace_count,
)

from _hypothesis_compat import given, settings, st

PROMPT_LEN = 8
MAX_LEN = 32          # shared by the lane and the reference loop
VOCAB = 512           # deepseek_7b smoke vocab (asserted below)


class _LM:
    """One smoke model + plain-decode reference, built once per module.

    deepseek_7b's smoke config is the ideal lane arch: fp32 activations
    (bit-exactness is meaningful), untied head (exercises the fused
    ``aug_head`` path), no frontend.
    """

    def __init__(self):
        cfg = get_smoke_config("deepseek_7b")
        assert not cfg.tie_embeddings and cfg.frontend is None
        assert cfg.vocab == VOCAB
        self.cfg = cfg
        self.model = Model(cfg)
        self.params = self.model.init(jax.random.key(0))
        self.embed = np.asarray(self.params["embed"], np.float32)
        self.head = np.asarray(self.params["head"], np.float32)
        self._prefill = jax.jit(make_prefill_step(self.model))
        self._decode = jax.jit(make_decode_step(self.model))
        self._plain_cache: dict[tuple[bytes, int], np.ndarray] = {}

    def registry(self, tenants: int, capacity: int | None = None):
        reg = LMSessionRegistry(
            self.cfg.vocab, self.cfg.d_model,
            capacity=capacity if capacity is not None else tenants,
        )
        for i in range(tenants):
            reg.register(f"t{i}", self.embed, seed=100 + i, head=self.head)
        return reg

    def plain_decode(self, prompt: np.ndarray, gen: int) -> np.ndarray:
        """Greedy generation on the raw (unmorphed) model — the reference
        the MoLe-delivered path must bit-match after unmorphing.  (MoLe is
        a conjugation by the vocab permutation: gathers move bits, so the
        equivalence is exact, not approximate.)"""
        key = (prompt.tobytes(), gen)
        if key not in self._plain_cache:
            caches = self.model.init_cache(1, MAX_LEN)
            logits, caches = self._prefill(
                self.params, {"tokens": jnp.asarray(prompt[None, :])}, caches
            )
            tok = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)[:, None]
            out = [int(tok[0, 0])]
            for i in range(gen - 1):
                logits, caches = self._decode(
                    self.params, tok,
                    jnp.asarray(prompt.size + i, jnp.int32), caches,
                )
                tok = jnp.argmax(logits[:, 0], axis=-1).astype(
                    jnp.int32
                )[:, None]
                out.append(int(tok[0, 0]))
            self._plain_cache[key] = np.asarray(out, np.int32)
        return self._plain_cache[key]


_CACHE: dict[str, _LM] = {}


def _lm() -> _LM:
    """Lazy module singleton: the hypothesis property can't take a fixture,
    and the model should be built once, not per example."""
    if "lm" not in _CACHE:
        _CACHE["lm"] = _LM()
    return _CACHE["lm"]


@pytest.fixture(scope="module")
def lm():
    return _lm()


def _prompts(rng, n):
    return [
        rng.integers(0, VOCAB, PROMPT_LEN).astype(np.int32) for _ in range(n)
    ]


def test_batched_decode_bit_matches_per_tenant_loop(lm, rng):
    """Every tenant decodes in one shared batched step; after the provider
    unmorph, each row is bit-identical to decoding that tenant alone on the
    plain model."""
    tenants = 4
    reg = lm.registry(tenants)
    lane = ContinuousDecodeLane(
        lm.model, lm.params, reg, rows=tenants, max_len=MAX_LEN
    )
    prompts = _prompts(rng, tenants)
    sids = [
        lane.submit(f"t{i}", prompts[i], max_new_tokens=6)
        for i in range(tenants)
    ]
    lane.run()
    for i, sid in enumerate(sids):
        np.testing.assert_array_equal(
            lane.take(sid), lm.plain_decode(prompts[i], 6)
        )


def test_join_leave_churn_is_exact_and_never_retraces(lm, rng):
    """More tenants than rows with ragged generation lengths: sequences
    retire and joiners are admitted mid-decode, every result stays exact,
    and the jitted decode step never retraces on churn."""
    tenants, rows = 8, 3
    reg = lm.registry(tenants)
    lane = ContinuousDecodeLane(
        lm.model, lm.params, reg, rows=rows, max_len=MAX_LEN
    )
    # Warm the step on a throwaway sequence (same prompt length as the
    # churn traffic: the decode step is shape-stable by construction, the
    # prefill compiles once per distinct prompt length).
    warm = lane.submit("t0", _prompts(rng, 1)[0], max_new_tokens=2)
    lane.run()
    lane.take(warm)

    n0 = delivery_trace_count()
    prompts = _prompts(rng, tenants)
    gens = [3, 6, 4, 8, 2, 5, 7, 3]
    sids = [
        lane.submit(f"t{i}", prompts[i], max_new_tokens=gens[i])
        for i in range(tenants)
    ]
    lane.run()
    assert delivery_trace_count() == n0, "decode lane retraced on churn"
    for i, sid in enumerate(sids):
        np.testing.assert_array_equal(
            lane.take(sid), lm.plain_decode(prompts[i], gens[i])
        )


@settings(max_examples=5, deadline=None)
@given(
    order=st.permutations(list(range(6))),
    gens=st.lists(st.integers(1, 7), min_size=6, max_size=6),
)
def test_any_join_order_stays_exact_property(order, gens):
    """Hypothesis sweep: arbitrary submission orders and generation lengths
    over a 2-row lane — join/leave scheduling never leaks one row's state
    into another (each result still bit-matches solo decoding)."""
    lm = _lm()
    rng = np.random.default_rng(7)
    prompts = _prompts(rng, 6)
    reg = lm.registry(6)
    lane = ContinuousDecodeLane(lm.model, lm.params, reg, rows=2,
                                max_len=MAX_LEN)
    sids = {}
    for i in order:
        sids[i] = lane.submit(f"t{i}", prompts[i], max_new_tokens=gens[i])
    lane.run()
    for i in order:
        np.testing.assert_array_equal(
            lane.take(sids[i]), lm.plain_decode(prompts[i], gens[i])
        )


@pytest.mark.parametrize("phase", ["retire", "admit"])
def test_crash_mid_decode_restores_exactly_once(lm, rng, phase):
    """Crash between decode steps (retire/admit boundary) after a snapshot:
    an in-place restore re-queues every unfinished sequence under its
    original seq_id, the deterministic replay regenerates identical tokens
    for active/queued/finished alike — exactly once — and nothing retraces
    across snapshot/restore."""
    tenants, rows = 6, 2
    reg = lm.registry(tenants)
    lane = ContinuousDecodeLane(
        lm.model, lm.params, reg, rows=rows, max_len=MAX_LEN
    )
    prompts = _prompts(rng, tenants)
    gens = [3, 6, 4, 5, 2, 4]
    sids = [
        lane.submit(f"t{i}", prompts[i], max_new_tokens=gens[i])
        for i in range(tenants)
    ]
    # Progress partway: some sequences finish, some are mid-decode, some
    # still queued — the mixed state a real crash interrupts.
    for _ in range(4):
        lane.step()
    assert 0 < lane.active and len(lane.queue) > 0
    snap = lane.snapshot()

    n0 = delivery_trace_count()
    lane.injector = FailureInjector(at_phases={phase})
    with pytest.raises(SimulatedFailure):
        lane.run()
    lane.injector = None

    restored = lane.restore(snap)
    assert set(restored) | set(snap.meta["finished"]) == set(sids)
    lane.run()
    assert delivery_trace_count() == n0, "decode lane retraced on restore"
    for i, sid in enumerate(sids):
        np.testing.assert_array_equal(
            lane.take(sid), lm.plain_decode(prompts[i], gens[i])
        )
        with pytest.raises(KeyError):   # exactly once: a second take fails
            lane.take(sid)


def test_admission_is_weighted_fair():
    """Saturated two-tenant backlog with 2:1 weights: the heavy tenant's
    sequences are admitted twice as often (WFQ charges max_new_tokens /
    weight service units per admission)."""
    q = FairAdmissionQueue()
    for i in range(12):
        q.submit("heavy", np.zeros(4, np.int32), 4, weight=2.0)
        q.submit("light", np.zeros(4, np.int32), 4, weight=1.0)
    taken = [q.take().tenant_id for _ in range(9)]
    assert taken.count("heavy") == 2 * taken.count("light")


def test_rows_beyond_capacity_share_slots(lm, rng):
    """Rows of one tenant share its slot, so capacity < rows works: a tenant
    without a slot waits until some slot holds no active row, no tenant is
    evicted mid-decode, and every result still bit-matches solo decoding."""
    reg = lm.registry(4, capacity=2)
    lane = ContinuousDecodeLane(lm.model, lm.params, reg, rows=4,
                                max_len=MAX_LEN)
    prompts = _prompts(rng, 6)
    owners = ["t0", "t0", "t1", "t2", "t3", "t1"]
    gens = [5, 3, 6, 2, 4, 3]
    sids = [lane.submit(t, p, max_new_tokens=g)
            for t, p, g in zip(owners, prompts, gens)]
    while lane.busy:
        lane.step()
        rows = [r for r in lane._row if r is not None]
        assert len({r.tenant_id for r in rows}) <= reg.capacity
        for r in rows:
            assert reg._slot_tenant[r.slot] == r.tenant_id
    assert lane.slot_waits >= 1     # t2/t3 queued behind t0/t1's rows
    for sid, p, g in zip(sids, prompts, gens):
        np.testing.assert_array_equal(lane.take(sid), lm.plain_decode(p, g))
    assert reg.evictions >= 2       # t2 and t3 took slots t0/t1 had freed


def test_submit_validation(lm):
    reg = lm.registry(1)
    lane = ContinuousDecodeLane(lm.model, lm.params, reg, rows=1,
                                max_len=MAX_LEN)
    with pytest.raises(ValueError, match="empty"):
        lane.submit("t0", np.zeros(0, np.int32), max_new_tokens=4)
    with pytest.raises(ValueError, match="max_len"):
        lane.submit(
            "t0", np.zeros(PROMPT_LEN, np.int32),
            max_new_tokens=MAX_LEN,
        )
    with pytest.raises(KeyError):
        lane.submit("nobody", np.zeros(4, np.int32), max_new_tokens=4)
