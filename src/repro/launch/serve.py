"""Serving driver: MoLe-secured delivery and LM serving, one delivery plane.

Three modes, all engine-backed:

``--mode delivery`` (default) — the batched multi-tenant delivery engine
(paper's training/inference data-delivery stage): many tenants register
sessions (own secret core + channel permutation), their requests coalesce
into padded microbatches, and morph + Aug-Conv run as one jitted batched
path (``repro.runtime.engine``).  Reports throughput vs the per-request
``MoLeSession.deliver`` baseline and verifies equivalence.

    PYTHONPATH=src python -m repro.launch.serve --mode delivery \
        --tenants 4 --requests 64 --batch 1 --kappa 4

``--mode lm`` — batched prefill + decode over a MoLe-secured token stream,
with the provider side served by the **same engine**: LM tenants register
in an ``LMSessionRegistry`` (each draws its own secret vocab permutation),
prompts coalesce into length-bucketed token microbatches, and the batched
multi-tenant morph runs as one jitted gather.  The developer serves each
tenant with that tenant's Aug-fused params; the provider unmorphs the
sampled tokens through the tenant's session.

    PYTHONPATH=src python -m repro.launch.serve --mode lm --arch deepseek_7b \
        --smoke --requests 8 --prompt-len 32 --gen 16 --mole token

``--mode serve`` — the **network front door** (``repro.launch.server``):
the async delivery engine behind a real TCP wire protocol
(``repro.runtime.wire``), with load shedding, deadline propagation,
exactly-once retry semantics, graceful drain on SIGTERM, and optional
network chaos.  Drive it with the load-generating client fleet
(``repro.launch.client``):

    PYTHONPATH=src python -m repro.launch.serve --mode serve --port 0 \
        --tenants 4 --kappa 2 --snapshot-dir /tmp/snap --stats
    PYTHONPATH=src python -m repro.launch.client --spawn-server --chaos \
        --requests 64 --report fleet-report.json

``--async`` works in the two **local** modes: traffic goes through the async front
door (``repro.runtime.async_engine``) — a background flusher with a
``--max-delay-ms`` latency SLO and per-tenant admission control
(``--max-inflight-rows``, ``--admission block|reject``); additionally
reports p50/p95 completion latency.

    PYTHONPATH=src python -m repro.launch.serve --mode delivery --async \
        --tenants 4 --requests 64 --max-delay-ms 5
    PYTHONPATH=src python -m repro.launch.serve --mode lm --arch deepseek_7b \
        --smoke --async --max-delay-ms 5 --admission reject

Flags that only make sense for the other mode are an error, not silently
ignored (``--batch`` with ``--mode lm``, ``--gen`` with ``--mode delivery``,
...).
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get_config, get_smoke_config
from repro.core.deploy import fuse_lm_params
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.models.api import Model
from repro.models.base import MoLeCfg


def _weights_of(args, tenants: int) -> list[float]:
    """--weights "2,1" cycled over the tenant count (all 1.0 by default)."""
    ws = [float(w) for w in args.weights.split(",")]
    if any(not w > 0 for w in ws):
        raise SystemExit(f"--weights must be positive, got {args.weights}")
    return [ws[i % len(ws)] for i in range(tenants)]


def _priorities_of(args, requests: int) -> list[int]:
    """--priority "0,1" cycled over the request count (all 0 by default)."""
    ps = [int(p) for p in args.priority.split(",")]
    return [ps[r % len(ps)] for r in range(requests)]


def _injector_of(args):
    """--inject-failure <phase> -> a one-shot FailureInjector (or None)."""
    if not args.inject_failure:
        return None
    from repro.runtime import FailureInjector

    return FailureInjector(at_phases={args.inject_failure})


def run_delivery(args) -> dict:
    """Serve image-delivery traffic for many tenants through the engine."""
    from repro.core import ConvGeometry, SessionRegistry
    from repro.runtime import (
        AsyncDeliveryEngine, DeliveryRequest, MoLeDeliveryEngine,
    )

    rng = np.random.default_rng(args.seed)
    geom = ConvGeometry(alpha=args.channels, beta=args.out_channels,
                        m=args.image_size, p=3)
    # Default the slot capacity to the tenant count: an exactly-sized slot
    # table keeps the steady-state "all tenants active" microbatch free of
    # padding groups (and on CPU, on the in-place arange fast case).
    capacity = args.capacity if args.capacity is not None else args.tenants
    registry = SessionRegistry(geom, kappa=args.kappa, capacity=capacity)
    fan_in = geom.alpha * geom.p * geom.p
    weights = _weights_of(args, args.tenants)
    for i in range(args.tenants):
        kernels = rng.standard_normal(
            (geom.alpha, geom.beta, geom.p, geom.p)
        ).astype(np.float32) / np.sqrt(fan_in)
        registry.register(f"tenant-{i}", kernels, weight=weights[i])

    engine = MoLeDeliveryEngine(registry, backend=args.backend or None)
    priorities = _priorities_of(args, args.requests)
    requests = [
        DeliveryRequest(
            f"tenant-{i % args.tenants}",
            rng.standard_normal((args.batch, geom.alpha, geom.m, geom.m))
            .astype(np.float32),
            priority=priorities[i], deadline_ms=args.deadline_ms,
        )
        for i in range(args.requests)
    ]

    # Warm both paths so we time steady-state serving, not compilation: the
    # engine warmup replays the full request pattern so the timed flush hits
    # the exact (G, B) buckets already compiled.
    for q in requests:
        engine.submit(q)
    engine.flush()
    for q in requests:
        jax.block_until_ready(
            registry.session(q.tenant_id).deliver(jnp.asarray(q.payload))
        )
    # Fresh stats so the report (latency quantiles, flush-phase timing)
    # describes the timed run, not the warmup's compilation.
    from repro.runtime import EngineStats

    engine.stats = EngineStats()
    engine.stats.service_share_fn = engine.scheduler.service_share

    if args.use_async:
        front = AsyncDeliveryEngine(
            engine, max_delay_ms=args.max_delay_ms,
            max_inflight_rows=args.max_inflight_rows, admission=args.admission,
            snapshot_dir=args.snapshot_dir,
            prefetch_horizon_ms=args.prefetch_horizon_ms,
            injector=_injector_of(args),
        )
        t0 = time.time()
        futures = [(r, front.submit(q)) for r, q in enumerate(requests)]
        feats = {r: f.result(timeout=120).payload for r, f in futures}
        dt_engine = time.time() - t0
        rids = [r for r, _ in futures]
        front.close()
    else:
        t0 = time.time()
        rids = [engine.submit(q) for q in requests]
        engine.flush()
        feats = {r: engine.take(r) for r in rids}
        dt_engine = time.time() - t0

    t0 = time.time()
    base = [
        np.asarray(
            registry.session(q.tenant_id).deliver(jnp.asarray(q.payload))
        )
        for q in requests
    ]
    dt_per_request = time.time() - t0

    n_images = args.requests * args.batch
    err = max(
        float(np.max(np.abs(feats[r] - base[i]))) for i, r in enumerate(rids)
    )
    stats = engine.stats
    latency = (
        f"  latency:     p50={stats.p50_ms:7.2f}ms p95={stats.p95_ms:7.2f}ms "
        f"(SLO max_delay={args.max_delay_ms}ms, {stats.flushes} flushes)\n"
        if args.use_async else ""
    )
    if args.use_async and (args.snapshot_dir or args.inject_failure):
        latency += (
            f"  resilience:  snapshots={stats.snapshots} "
            f"degraded_flushes={stats.degraded_flushes} "
            f"injected={args.inject_failure or 'none'}\n"
        )
    print(
        f"delivery tenants={args.tenants} requests={args.requests} "
        f"batch={args.batch} kappa={args.kappa} backend={engine.backend} "
        f"async={args.use_async}\n"
        f"  engine:      {n_images / dt_engine:9.1f} images/s "
        f"({stats.microbatches} microbatches, "
        f"padding {stats.padding_fraction:.0%})\n"
        f"{latency}"
        f"  per-request: {n_images / dt_per_request:9.1f} images/s\n"
        f"  speedup:     {dt_per_request / dt_engine:9.2f}x   "
        f"max |engine - per-request| = {err:.2e}"
    )
    if args.stats:
        print("engine stats:")
        for line in stats.summary().splitlines():
            print(f"  {line}")
    out = {
        "images_per_s_engine": n_images / dt_engine,
        "images_per_s_per_request": n_images / dt_per_request,
        "speedup": dt_per_request / dt_engine,
        "max_err": err,
    }
    if args.use_async:
        out["p50_ms"] = stats.p50_ms
        out["p95_ms"] = stats.p95_ms
    return out


def run_lm(args) -> np.ndarray:
    """Serve LM traffic: engine-morphed prompts, per-tenant Aug-fused serving.

    Provider side (the delivery engine): each LM tenant holds its own secret
    vocab permutation in the shared ``LMSessionRegistry``; prompt requests
    coalesce into length-bucketed token microbatches and morph as one jitted
    multi-tenant gather — sync flush or the async deadline flusher.
    Developer side: plain LMs decode through the continuous-batched
    cross-tenant :class:`~repro.runtime.decode.ContinuousDecodeLane` (one
    shared batched step over all tenants' rows, fed by the registry's
    stacked AugE tables / Aug-heads); frontend/audio models fall back to
    per-tenant Aug-fused prefill + decode.  Provider unmorphs the sampled
    tokens.

    Returns the unmorphed generations, request-ordered — with ``--tenants 1``
    bit-identical to the pre-engine single-``TokenMorpher`` path.
    """
    from repro.core.lm import LMSessionRegistry
    from repro.runtime import (
        AsyncDeliveryEngine, ContinuousDecodeLane, DeliveryRequest,
        MoLeDeliveryEngine,
    )

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    use_mole = args.mole != "off"
    if use_mole:
        cfg = dataclasses.replace(cfg, mole=MoLeCfg(enabled=True, mode="token"))
    model = Model(cfg)
    params = model.init(jax.random.key(args.seed))
    embed = np.asarray(
        params["dec"]["embed"] if cfg.family == "audio" else params["embed"],
        np.float32,
    )

    tenants = max(1, min(args.tenants, args.requests))
    src = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=args.prompt_len,
                                 global_batch=args.requests, seed=args.seed))
    raw_prompts = np.asarray(src.batch(0)["tokens"])
    tenant_of = [f"lm-{i % tenants}" for i in range(args.requests)]

    # ---- provider side: engine-morphed prompts ---------------------------
    registry = engine = None
    stats = None
    if use_mole:
        capacity = args.capacity if args.capacity is not None else tenants
        registry = LMSessionRegistry(
            cfg.vocab, embed.shape[1], capacity=capacity
        )
        weights = _weights_of(args, tenants)
        head = (
            None
            if cfg.tie_embeddings or cfg.family == "audio"
            else np.asarray(params["head"], np.float32)
        )
        for i in range(tenants):
            # Tenant lm-0 draws the same secret as the pre-engine single-
            # morpher path (seed = cfg.mole.seed), so --tenants 1 reproduces
            # it bit-for-bit; other tenants offset the seed.
            registry.register(
                f"lm-{i}", embed, seed=cfg.mole.seed + i, weight=weights[i],
                head=head,
            )
        engine = MoLeDeliveryEngine(
            lm_registry=registry, backend=args.backend or None,
            # Make --prompt-len itself a seq bucket: any prompt length is
            # servable and the steady-state microbatch carries zero
            # sequence padding.
            seq_buckets=tuple(
                sorted({8, 16, 32, 64, 128, 256, 512, args.prompt_len})
            ),
        )
        priorities = _priorities_of(args, args.requests)
        prompt_reqs = [
            DeliveryRequest(
                tenant_of[r], raw_prompts[r : r + 1], lane="tokens",
                priority=priorities[r], deadline_ms=args.deadline_ms,
            )
            for r in range(args.requests)
        ]
        t0 = time.time()
        if args.use_async:
            front = AsyncDeliveryEngine(
                engine, max_delay_ms=args.max_delay_ms,
                max_inflight_rows=args.max_inflight_rows,
                admission=args.admission,
                snapshot_dir=args.snapshot_dir,
                prefetch_horizon_ms=args.prefetch_horizon_ms,
                injector=_injector_of(args),
            )
            futures = [front.submit(q) for q in prompt_reqs]
            served_prompts = np.concatenate(
                [f.result(timeout=120).payload for f in futures], axis=0
            )
            front.close()
        else:
            rids = [engine.submit(q) for q in prompt_reqs]
            engine.flush()
            served_prompts = np.concatenate(
                [engine.take(r) for r in rids], axis=0
            )
        dt_morph = time.time() - t0
        stats = engine.stats
    else:
        served_prompts = raw_prompts
        dt_morph = 0.0

    # ---- developer side ---------------------------------------------------
    max_len = args.prompt_len + args.gen + 1
    final = np.zeros((args.requests, args.gen), np.int64)
    use_lane = use_mole and cfg.frontend is None and cfg.family != "audio"
    if use_lane:
        # Continuous-batched cross-tenant decode: every request becomes a
        # lane row; all tenants decode in one shared batched step against
        # the registry's stacked AugE tables / Aug-heads, and finished rows
        # hand their slot to the next queued request between steps.  The
        # lane unmorphs on take(), so `final` is already the provider view.
        t0 = time.time()
        # The lane shares the delivery engine's FairScheduler: a tenant's
        # decode appetite (max_new_tokens steps per admission) charges the
        # same engine-wide clock as its prompt-morph traffic, so weights
        # hold across the whole serving path, not per lane.
        lane = ContinuousDecodeLane(
            model, params, registry,
            rows=min(args.requests, registry.capacity),
            max_len=max_len, backend=args.backend or None,
            scheduler=engine.scheduler,
        )
        sids = [
            lane.submit(
                tenant_of[r], served_prompts[r], args.gen,
                priority=priorities[r], premorphed=True,
            )
            for r in range(args.requests)
        ]
        lane.run()
        for r, sid in enumerate(sids):
            final[r] = lane.take(sid)
        dt = time.time() - t0
    else:
        # Frontend/audio (or mole=off) fallback: Aug-fused params, prefill
        # + greedy decode one tenant group at a time.
        prefill = jax.jit(make_prefill_step(model))
        decode = jax.jit(make_decode_step(model), donate_argnums=(3,))
        by_tenant: dict[str, list[int]] = {}
        for r, t in enumerate(tenant_of):
            by_tenant.setdefault(t if use_mole else "all", []).append(r)

        t0 = time.time()
        for t, ridx in by_tenant.items():
            sess = registry.session(t) if use_mole else None
            dev_params = (
                fuse_lm_params(params, cfg, token_morpher=sess.morpher)
                if use_mole else params
            )
            batch = {"tokens": jnp.asarray(served_prompts[ridx], jnp.int32)}
            if cfg.frontend is not None:
                key = "frames" if cfg.frontend.kind == "audio" else "patches"
                batch[key] = jnp.zeros(
                    (len(ridx), cfg.frontend.n_tokens, cfg.frontend.d_in),
                    jnp.bfloat16,
                )
            caches = model.init_cache(len(ridx), max_len)
            logits, caches = prefill(dev_params, batch, caches)
            tok = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)[:, None]
            out_tokens = [tok]
            for i in range(args.gen - 1):
                step_t = jnp.asarray(args.prompt_len + i, jnp.int32)
                logits, caches = decode(dev_params, tok, step_t, caches)
                tok = jnp.argmax(logits[:, 0], axis=-1).astype(
                    jnp.int32
                )[:, None]
                out_tokens.append(tok)
            served_out = np.concatenate(
                [np.asarray(tk) for tk in out_tokens], axis=1
            )
            # ---- provider side: unmorph this tenant's served tokens ------
            final[ridx] = (
                np.asarray(sess.morpher.inv_perm)[served_out]
                if use_mole else served_out
            )
        dt = time.time() - t0

    tps = args.requests * args.gen / dt
    engine_line = ""
    if use_mole:
        engine_line = (
            f"  engine morph: {args.requests / max(dt_morph, 1e-9):9.1f} "
            f"prompts/s ({stats.microbatches} microbatches, "
            f"padding {stats.padding_fraction:.0%}, async={args.use_async}"
        )
        if args.use_async:
            engine_line += (
                f", p50={stats.p50_ms:.2f}ms p95={stats.p95_ms:.2f}ms"
            )
        engine_line += ")\n"
    # analysis: declassified(demo CLI prints the provider-view generation - unmorphed output data, not key material)
    print(
        f"arch={cfg.name} requests={args.requests} tenants={tenants} "
        f"gen={args.gen} mole={'token' if use_mole else 'off'}  "
        f"{dt:.2f}s  {tps:.1f} tok/s\n"
        f"{engine_line}"
        f"first request generation (provider view): "
        f"{final[0][:12].tolist()}"
    )
    if use_mole and args.stats:
        print("engine stats:")
        for line in stats.summary().splitlines():
            print(f"  {line}")
    return final


# Mode gating: CLI spelling -> (argparse dest, default, modes that accept
# it).  Giving a flag outside its modes is an error, not a silent drop —
# silently ignored flags hid real misconfigurations (the old --mode lm
# ignored --async entirely).
_MODES = ("delivery", "lm", "serve")
_FLAGS = {
    # vision geometry: the batched delivery lane (local run or served)
    "--batch": ("batch", 1, ("delivery",)),
    "--kappa": ("kappa", 1, ("delivery", "serve")),
    "--channels": ("channels", 3, ("delivery", "serve")),
    "--out-channels": ("out_channels", 16, ("delivery", "serve")),
    "--image-size": ("image_size", 16, ("delivery", "serve")),
    # lm-only
    "--arch": ("arch", None, ("lm",)),
    "--smoke": ("smoke", False, ("lm",)),
    "--prompt-len": ("prompt_len", 32, ("lm",)),
    "--gen": ("gen", 16, ("lm",)),
    "--mole": ("mole", "token", ("lm",)),
    # delivery engine / async front door (under --mode lm --mole off no
    # engine runs at all, so these error there too — checked separately)
    "--tenants": ("tenants", 4, _MODES),
    "--backend": ("backend", None, _MODES),
    "--async": ("use_async", False, ("delivery", "lm")),
    "--max-delay-ms": ("max_delay_ms", 5.0, _MODES),
    "--max-inflight-rows": ("max_inflight_rows", 4096, _MODES),
    "--admission": ("admission", "block", ("delivery", "lm")),
    "--capacity": ("capacity", None, _MODES),
    "--stats": ("stats", False, _MODES),
    "--weights": ("weights", "1", _MODES),
    "--priority": ("priority", "0", ("delivery", "lm")),
    "--deadline-ms": ("deadline_ms", None, ("delivery", "lm")),
    "--snapshot-dir": ("snapshot_dir", None, _MODES),
    "--inject-failure": ("inject_failure", None, _MODES),
    "--prefetch-horizon-ms": ("prefetch_horizon_ms", None, _MODES),
    # serve-only: the network front door (launch/server.py).  serve is
    # always async (--async errors), always admission=reject (--admission
    # errors: shedding must be a typed frame, not submitter backpressure),
    # and per-request priority/deadline arrive on the wire (--priority /
    # --deadline-ms error).
    "--host": ("host", "127.0.0.1", ("serve",)),
    "--port": ("port", 0, ("serve",)),
    "--max-pending-rows": ("max_pending_rows", 4096, ("serve",)),
    "--read-timeout-ms": ("read_timeout_ms", 30000.0, ("serve",)),
    "--write-timeout-ms": ("write_timeout_ms", 10000.0, ("serve",)),
    "--drain-timeout-ms": ("drain_timeout_ms", 30000.0, ("serve",)),
    "--warm-batch": ("warm_batch", 8, ("serve",)),
    "--chaos": ("chaos", False, ("serve",)),
    "--chaos-rate": ("chaos_rate", 0.2, ("serve",)),
    "--chaos-seed": ("chaos_seed", 0, ("serve",)),
}
# The engine/front-door subset, for the --mode lm --mole off check.
_ENGINE_FLAGS = (
    "--tenants", "--backend", "--async", "--max-delay-ms",
    "--max-inflight-rows", "--admission", "--capacity", "--stats",
    "--weights", "--priority", "--deadline-ms", "--snapshot-dir",
    "--inject-failure", "--prefetch-horizon-ms",
)


def parse_args(argv=None) -> argparse.Namespace:
    """Parse and validate serve.py flags; every unset flag takes its mode's
    default and ``args.mode`` is resolved.  ``build_front`` and in-process
    servers take the result as is."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default=None, choices=list(_MODES),
                    help="default: lm when --arch is given, else delivery; "
                         "serve = network front door (launch/server.py)")
    ap.add_argument("--arch", default=None, choices=ARCHS)
    # delivery-engine options (both modes, but require the engine: error
    # under --mode lm --mole off)
    ap.add_argument("--tenants", type=int, default=None)
    ap.add_argument("--backend", default=None,
                    help="kernel backend: pallas | interpret | jnp (default auto)")
    ap.add_argument("--async", dest="use_async", action="store_true",
                    default=None,
                    help="serve through the async front door (deadline "
                         "flusher + admission control)")
    ap.add_argument("--max-delay-ms", type=float, default=None,
                    help="async latency SLO: max wait before a flush fires")
    ap.add_argument("--max-inflight-rows", type=int, default=None,
                    help="async per-tenant admission quota (rows in flight)")
    ap.add_argument("--admission", default=None, choices=["block", "reject"],
                    help="over-quota behavior: backpressure or AdmissionError")
    ap.add_argument("--capacity", type=int, default=None,
                    help="registry slot capacity (default: one slot per "
                         "--tenants, which minimizes padding groups; "
                         "tenants beyond capacity LRU-evict to host — the "
                         "grouped kernels serve any slot layout at the "
                         "same cost)")
    ap.add_argument("--stats", action="store_true", default=None,
                    help="print the engine stats summary after the run "
                         "(flush-phase p50/p95, per-priority latency, "
                         "admission accounting, WFQ lag, submit stalls)")
    ap.add_argument("--weights", default=None, metavar="W0,W1,...",
                    help="per-tenant WFQ weights, cycled over the tenant "
                         "count (default: every tenant weight 1); a weight-2 "
                         "tenant receives ~2x a weight-1 tenant's rows "
                         "under saturation")
    ap.add_argument("--priority", default=None, metavar="P0,P1,...",
                    help="per-request priorities, cycled over the request "
                         "count (default 0; higher dequeues first within a "
                         "tenant) — --stats splits latency per priority")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline put on every DeliveryRequest "
                         "(overrides --max-delay-ms per request; requires "
                         "--async)")
    ap.add_argument("--snapshot-dir", default=None, metavar="DIR",
                    help="persist an engine snapshot between flush rounds "
                         "for crash recovery (atomic CheckpointManager "
                         "layout; requires --async)")
    ap.add_argument("--inject-failure", default=None,
                    choices=["coalesce", "device", "publish"],
                    help="crash the flusher once at this flush phase to "
                         "exercise supervised recovery (requires --async)")
    ap.add_argument("--prefetch-horizon-ms", type=float, default=None,
                    help="enable predictive prefetch: after each flush "
                         "round the async flusher stages evicted tenants "
                         "the arrival predictor expects within this "
                         "horizon (requires --async; hit rate in --stats)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    # vision-delivery-only options (error under --mode lm)
    ap.add_argument("--batch", type=int, default=None,
                    help="[delivery] images per delivery request")
    ap.add_argument("--kappa", type=int, default=None)
    ap.add_argument("--channels", type=int, default=None)
    ap.add_argument("--out-channels", type=int, default=None)
    ap.add_argument("--image-size", type=int, default=None)
    # lm-only options (error under --mode delivery / serve)
    ap.add_argument("--smoke", action="store_true", default=None)
    ap.add_argument("--prompt-len", type=int, default=None)
    ap.add_argument("--gen", type=int, default=None)
    ap.add_argument("--mole", default=None, choices=["off", "token"])
    # serve-only options (the network front door; error elsewhere)
    ap.add_argument("--host", default=None,
                    help="[serve] bind address (default 127.0.0.1)")
    ap.add_argument("--port", type=int, default=None,
                    help="[serve] TCP port; 0 picks an ephemeral one, "
                         "printed as 'serving on host:port'")
    ap.add_argument("--max-pending-rows", type=int, default=None,
                    help="[serve] global load-shed threshold: admitted-but-"
                         "uncompleted rows beyond this get a typed "
                         "OVERLOADED rejection (0 disables)")
    ap.add_argument("--read-timeout-ms", type=float, default=None,
                    help="[serve] per-connection read timeout: a client "
                         "stalled mid-frame loses its connection")
    ap.add_argument("--write-timeout-ms", type=float, default=None,
                    help="[serve] per-connection write/drain timeout")
    ap.add_argument("--drain-timeout-ms", type=float, default=None,
                    help="[serve] graceful-drain budget on SIGTERM")
    ap.add_argument("--warm-batch", type=int, default=None,
                    help="[serve] rows per tenant in the warmup flush "
                         "(pre-compiles the steady-state buckets)")
    ap.add_argument("--chaos", action="store_true", default=None,
                    help="[serve] arm server-side network chaos: dropped "
                         "accepts, requests lost after read, truncated/"
                         "stalled writes")
    ap.add_argument("--chaos-rate", type=float, default=None,
                    help="[serve] per-event probability for --chaos")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="[serve] RNG seed for --chaos")
    # Every None-default flag must belong to the gating table — otherwise a
    # future flag would silently stay None in every mode, the
    # misconfiguration class this validation exists to kill.
    gated = {dest for dest, _, _ in _FLAGS.values()}
    ungated = {
        a.dest for a in ap._actions
        if a.default is None and a.dest not in ("help", "mode")
    } - gated
    assert not ungated, f"flags missing from the mode-gating table: {ungated}"
    args = ap.parse_args(argv)

    mode = args.mode or ("lm" if args.arch else "delivery")
    for flag, (dest, _, modes) in _FLAGS.items():
        if mode not in modes and getattr(args, dest) is not None:
            ap.error(
                f"{flag} only applies to --mode {'/'.join(modes)} "
                f"(got --mode {mode})"
            )
    if mode == "lm" and args.mole == "off":
        for flag in _ENGINE_FLAGS:
            dest = _FLAGS[flag][0]
            if getattr(args, dest) is not None:
                ap.error(
                    f"{flag} requires the delivery engine, which --mole off "
                    f"disables"
                )
    # --deadline-ms arms the async flusher's per-request deadlines; without
    # --async nothing ever reads it — error, not a silent no-op.  (serve is
    # always async: these checks apply to the local modes only.)
    if args.deadline_ms is not None and not args.use_async:
        ap.error("--deadline-ms requires --async (the deadline flusher)")
    # Snapshotting and failure injection live in the supervised background
    # flusher; the sync path has no flusher to crash or supervise.
    if mode != "serve":
        if args.snapshot_dir is not None and not args.use_async:
            ap.error("--snapshot-dir requires --async (the supervised "
                     "flusher)")
        if args.inject_failure is not None and not args.use_async:
            ap.error("--inject-failure requires --async (the supervised "
                     "flusher)")
        if args.prefetch_horizon_ms is not None and not args.use_async:
            ap.error("--prefetch-horizon-ms requires --async (predictive "
                     "prefetch runs in the background flusher's slack)")
    if args.chaos is None and (
        args.chaos_rate is not None or args.chaos_seed is not None
    ):
        ap.error("--chaos-rate/--chaos-seed require --chaos")
    if mode == "lm" and args.arch is None:
        ap.error("--arch is required with --mode lm")
    for dest, default, _ in _FLAGS.values():
        if getattr(args, dest) is None:
            setattr(args, dest, default)
    args.mode = mode
    return args


def main(argv=None):
    args = parse_args(argv)
    if args.mode == "serve":
        from repro.launch.server import run_serve

        return run_serve(args)
    if args.mode == "delivery":
        return run_delivery(args)
    return run_lm(args)


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
