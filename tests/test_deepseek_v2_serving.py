"""DeepSeek-V2 served through the front door at the smoke size: generations
over TCP from several LM tenants against the plain float32 reference
(``models/reference.py``), the dropless expert layer against the
dense-masked MoE, and YaRN against its formula."""
import asyncio
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.kernels.ops import expert_ffn
from repro.launch.serve import parse_args
from repro.launch.server import build_front, close_front, make_server
from repro.models import blocks as B
from repro.models import layers as L
from repro.models import reference as R
from repro.runtime import DeliveryRequest, wire

ARCH = "deepseek_v2_lite_16b"


def _flags(**kw):
    base = {"--tenants": 3, "--capacity": 2, "--rows": 4, "--max-len": 64,
            "--prompt-buckets": "8,16,32", "--backend": "jnp", "--seed": 7}
    base.update(kw)
    out = ["--mode", "serve", "--port", "0", "--arch", ARCH, "--smoke"]
    for k, v in base.items():
        out += [k, str(v)]
    return out


@pytest.fixture(scope="module")
def served():
    """Six generations of different lengths from three tenants (two slots,
    four rows), sent over TCP; the front door's weights kept for the
    reference."""
    args = parse_args(_flags())
    front = build_front(args)
    server = make_server(front, args)
    rng = np.random.default_rng(3)
    asks = [(f"tenant-{i % 3}", rng.integers(0, 512, 5 + 3 * i).astype(np.int32),
             4 + i) for i in range(6)]

    async def go():
        await server.start()
        reader, writer = await asyncio.open_connection("127.0.0.1", server.port)
        for i, (t, p, n) in enumerate(asks):
            writer.write(wire.encode_request(
                DeliveryRequest(t, p, lane="decode", max_new_tokens=n), str(i)))
        await writer.drain()
        got = {}
        while len(got) < len(asks):
            kind, header, payload = await wire.read_frame(reader)
            assert kind == wire.KIND_RES, header
            got[header["rid"]] = wire.decode_result(header, payload)
        writer.close()
        await server.drain_and_stop(timeout=30.0)
        return got

    got = asyncio.run(go())
    eng = front.engine
    weights = {
        "params": eng.decode_lane.params,
        "embed": eng.lm_registry.session("tenant-0").embedding,
        "head": eng.lm_registry.session("tenant-0").head,
        "cfg": eng.decode_lane.model.cfg,
        "waits": eng.stats.decode_slot_waits,
    }
    assert close_front(front, 0) == 0
    return asks, got, weights


def test_generations_over_tcp_match_the_reference(served):
    """Prefill, then decode through the cache, across tenants that share
    two slots: each generated id is the reference's argmax at its position,
    and the logit the server sent with it is the reference's there."""
    asks, got, w = served
    cfg = w["cfg"]
    for i, (_, prompt, n) in enumerate(asks):
        res = got[str(i)]
        ids, scores = res.payload, res.scores
        assert ids.shape == (n,) and scores.shape == (n,)
        text = np.concatenate([prompt, ids[:-1]])
        logits = np.asarray(R.forward(w["params"], w["embed"], w["head"], text,
                                      cfg))[prompt.size - 1:]
        np.testing.assert_array_equal(ids, logits.argmax(-1))
        picked = logits[np.arange(n), ids]
        np.testing.assert_allclose(scores, picked, rtol=1e-4, atol=1e-5)


def test_rows_of_three_tenants_waited_for_two_slots(served):
    assert served[2]["waits"] >= 1


def _round(eng) -> bool:
    """One flush round of the engine's three phases; False when idle."""
    work = eng.begin_flush()
    if work is None:
        return False
    eng.execute_flush(work)
    eng.publish_flush(work)
    return True


def test_a_tenant_arriving_while_every_slot_is_pinned_is_served():
    """Two slots, both pinned by other tenants' decoding rows, when a third
    tenant's generation and tokens request arrive; the other two keep
    sending.  Nothing fails: the third tenant's requests wait in their
    queues, admission stops until a slot drains, and its generation is the
    reference's while the others' traffic still flows."""
    from repro.launch.server import _build_lm_engine

    args = parse_args(_flags())
    eng, warm, _ = _build_lm_engine(args)
    while _round(eng):
        pass
    for rid in warm:
        eng.take(rid)
    reg, rng = eng.lm_registry, np.random.default_rng(5)
    prompt = lambda: rng.integers(0, 512, 8).astype(np.int32)
    gen = lambda t, n: eng.submit(DeliveryRequest(
        f"tenant-{t}", prompt(), lane="decode", max_new_tokens=n))
    busy = [gen(0, 12), gen(1, 12)]
    while len(reg._pinned) < 2 and _round(eng):
        pass
    assert reg._pinned == {"tenant-0", "tenant-1"} and reg.capacity == 2
    late = prompt()
    mine = eng.submit(DeliveryRequest("tenant-2", late, lane="decode",
                                      max_new_tokens=5))
    toks = eng.submit(DeliveryRequest("tenant-2", late[None], lane="tokens"))
    for i in range(60):
        busy += [gen(0, 6), gen(1, 6)]          # steady traffic from both
        _round(eng)
        if {mine, toks} <= eng._done:
            break
    assert {mine, toks} <= eng._done, "the third tenant starved"
    assert eng.stats.decode_slot_waits >= 1
    ids = eng.take(mine)
    perm = reg.session("tenant-2").morpher.perm
    np.testing.assert_array_equal(eng.take(toks)[0], perm[late])
    lane = eng.decode_lane
    text = np.concatenate([late, ids[:-1]])
    logits = np.asarray(R.forward(
        lane.params, reg.session("tenant-0").embedding,
        reg.session("tenant-0").head, text, lane.model.cfg))[late.size - 1:]
    np.testing.assert_array_equal(ids, logits.argmax(-1))
    while _round(eng):
        pass
    assert all(r in eng._done for r in busy)


def _moe_params(cfg, key):
    from repro.models.base import init_params

    return init_params(key, B.schema_moe(cfg), jnp.float32)


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_dropless_experts_match_the_dense_masked_moe(backend):
    """Every token routed to one expert first (a capacity-buffer MoE drops
    most of them): the dropless layer serves them all, as the reference's
    every-expert-masked MoE does."""
    cfg = get_smoke_config(ARCH)
    p = _moe_params(cfg, jax.random.key(0))
    hot = jnp.zeros_like(p["router"]).at[0, 3].set(1.0)
    p = dict(p, router=hot)
    x = jax.random.normal(jax.random.key(1), (40, cfg.d_model), jnp.float32)
    x = x.at[:, 0].set(jnp.abs(x[:, 0]) + 3.0)     # the hot expert wins
    top_p, top_i = B.moe_route(p, x, cfg)
    assert bool(jnp.all(top_i[:, 0] == 3))
    y = expert_ffn(x, top_i, top_p, p["wg"], p["wu"], p["wd"], act=cfg.act,
                   backend=backend)
    shared = p["shared"]
    y = y + B.apply_ffn(shared, x, cfg)
    with jax.default_matmul_precision("highest"):
        want = R.moe_dense(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-4,
                               atol=1e-5)
    got, hit = B.moe_dropless(p, x, cfg, backend=backend)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-5)
    assert int(hit) == len(set(np.asarray(top_i).ravel().tolist()))


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_tokens_left_out_route_to_no_expert(backend):
    """A decode step's idle rows and a prefill's padding are left out of the
    routing: the kept tokens' outputs are as without the mask, the others'
    are zeros, and only the kept tokens' experts count as hit."""
    cfg = get_smoke_config(ARCH)
    p = _moe_params(cfg, jax.random.key(2))
    x = jax.random.normal(jax.random.key(3), (24, cfg.d_model), jnp.float32)
    keep = jnp.arange(24) < 5
    full, _ = B.moe_dropless(p, x, cfg, backend=backend)
    got, hit = B.moe_dropless(p, x, cfg, backend=backend, keep=keep)
    shared = B.apply_ffn(p["shared"], x, cfg)
    np.testing.assert_allclose(np.asarray(got[:5]), np.asarray(full[:5]),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got[5:]), np.asarray(shared[5:]),
                               rtol=1e-6, atol=1e-6)
    _, top_i = B.moe_route(p, x, cfg)
    assert int(hit) == len(set(np.asarray(top_i[:5]).ravel().tolist()))


def test_yarn_matches_its_formula():
    """DeepSeek-V2-Lite's rope_scaling: frequencies blended by the linear
    ramp between the beta_fast and beta_slow correction dimensions, and the
    softmax scale times mscale(40, 0.707) squared."""
    full = get_config(ARCH)
    y, dim, base = full.rope_scaling, full.mla.qk_rope, full.rope_theta
    i = np.arange(dim // 2)
    extra = base ** (-2.0 * i / dim)
    # correction dims: d(r) = dim * ln(4096 / (2 pi r)) / (2 ln base)
    lo = math.floor(dim * math.log(4096 / (2 * math.pi * 32)) / (2 * math.log(base)))
    hi = math.ceil(dim * math.log(4096 / (2 * math.pi * 1)) / (2 * math.log(base)))
    ramp = np.clip((i - lo) / (hi - lo), 0, 1)
    want = extra * (1 - ramp) + extra / 40 * ramp
    np.testing.assert_allclose(L.rope_inv_freq(dim, base, y), want, rtol=1e-6)
    assert (lo, hi) == (10, 23)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert B.mla_softmax_scale(full) == pytest.approx(192 ** -0.5 * m * m)
    assert m * m == pytest.approx(1.59, abs=0.005)
    # Equal mscale and mscale_all_dim: cos and sin are not rescaled.
    x = jnp.ones((1, 3, 1, dim), jnp.float32)
    pos = jnp.arange(3)[None]
    a = L.rope(x, pos, base, y)
    theta = np.asarray(pos)[0, :, None] * want[None]
    np.testing.assert_allclose(np.asarray(a)[0, :, 0, : dim // 2],
                               np.cos(theta) - np.sin(theta), rtol=1e-5,
                               atol=1e-6)


def test_large_leaves_are_drawn_block_by_block(monkeypatch):
    """A leaf above the block size (a full-size expert stack) is drawn a
    block at a time into one buffer: seeded, every block its own draw,
    none left unwritten, with the leaf's scale."""
    from repro.models import base

    monkeypatch.setattr(base, "_DRAW_SLICE", 1000)
    key = jax.random.key(3)
    a = np.asarray(base._normal(key, (3, 5, 40, 30), 0.5, jnp.bfloat16),
                   np.float32)
    b = np.asarray(base._normal(key, (3, 5, 40, 30), 0.5, jnp.bfloat16),
                   np.float32)
    np.testing.assert_array_equal(a, b)
    mats = a.reshape(15, -1)
    assert len({m.tobytes() for m in mats}) == 15
    assert np.all(np.abs(mats).sum(-1) > 0)
    assert abs(a.std() - 0.5) < 0.02 and abs(a.mean()) < 0.02
