#!/usr/bin/env python3
"""Chip smoke check: the served delivery path once, on one TPU, at the
paper's VGG-16/CIFAR first-layer geometry (alpha=3, m=32, p=3, beta=64).

    python chip_smoke.py [--seed N]

One process, through the entry points a user calls.  serve.py's flags build
the front door (``launch/server.build_front``: 4 tenants, capacity 4,
kappa=1, each with its own secret morph core and a 3072 x 65536 Aug-Conv
matrix, warmed), a ``DeliveryServer`` serves it on an ephemeral localhost
port, and the client fleet (``launch/client``) sends 16 requests of 8
images from 4 connections, twice: once to compile every microbatch shape
the trace produces, once more to time.  The script then checks that

  * every request resolved exactly once and ``ok``: no rejection, no shed,
    no failed flush, nothing lost in the drain, a live flusher;
  * every served feature map equals a plain float32 convolution of the
    unmorphed images with the tenant's kernels, permuted by the tenant's
    secret channel permutation (paper eq. 5), within ``TOL``;
  * the served step ran the Pallas kernels: two ``tpu_custom_call``s in
    the step lowered at each microbatch shape the fleets used;
  * one fp32 product in a Pallas kernel, through the kernels' own
    ``mxu_dot``, is fp32-accurate.  The same product with a plain
    ``jnp.dot`` at Mosaic's default precision is printed beside it: the
    control that shows why ``mxu_dot`` asks for ``Precision.HIGHEST``.

It prints set-up seconds, peak device bytes and the client-side images/s
(informative, not a benchmark).  It exits non-zero, and prints no result
line, when JAX finds no TPU or the kernel backend would not be Pallas.  The
last line of its output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.launch.client import ClientFleet, FleetConfig  # noqa: E402

# Largest allowed |served - reference| over every delivered feature.  Images
# are N(0, 1) and kernels are scaled by 1/sqrt(fan-in), so features are of
# unit scale.  Morph and Aug-Conv are two fp32 contractions over 3072 terms
# each, which keeps errors near 1e-5; a bf16 MXU pass anywhere on the way
# would put them near 1e-2.  1e-3 tells the two apart.
TOL = 1e-3

PAPER_FLAGS = [
    "--channels", "3", "--out-channels", "64", "--image-size", "32",
    "--kappa", "1", "--tenants", "4", "--capacity", "4", "--warm-batch", "8",
]


class RecordingFleet(ClientFleet):
    """The client fleet, keeping each request's images and the features
    served for it, for the reference check."""

    def __init__(self, cfg: FleetConfig):
        super().__init__(cfg)
        self.sent: dict[str, object] = {}
        self.served: dict[str, np.ndarray] = {}

    def _make_request(self, idx: int):
        req = super()._make_request(idx)
        self.sent[f"{self.cfg.fleet_id}-{idx}"] = req   # the fleet's rid
        return req

    def _on_result(self, res) -> None:
        super()._on_result(res)
        self.served.setdefault(res.rid, res.payload)


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip smoke failed: {what}")


def _conv_reference(geom):
    """Plain float32 first-layer convolution, independent of ``kernels/``."""
    import jax
    from jax import lax

    @jax.jit
    def conv(images, kernels):
        return lax.conv_general_dilated(
            images, kernels.transpose(1, 0, 2, 3),        # -> OIHW
            window_strides=(geom.stride, geom.stride),
            padding=[(geom.pad, geom.pad)] * 2,
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            precision=lax.Precision.HIGHEST,
        )

    return conv


def _custom_calls_per_step(engine, shapes) -> dict:
    """``tpu_custom_call``s in ``_delivery_step`` lowered at each (G, B)."""
    import jax
    import jax.numpy as jnp

    from repro.runtime.engine import _delivery_step

    reg = engine.registry
    g, S, kappa = reg.geom, reg.capacity, reg.kappa
    q = g.in_features // kappa
    f32 = jnp.float32
    out = {}
    for G, B in sorted(shapes):
        text = _delivery_step.lower(
            jax.ShapeDtypeStruct((G, B, g.in_features), f32),
            jax.ShapeDtypeStruct((G,), jnp.int32),
            jax.ShapeDtypeStruct((S, q, q), f32),
            jax.ShapeDtypeStruct((S, g.in_features, g.out_features), f32),
            kappa=kappa, backend=engine.backend,
        ).as_text()
        out[(G, B)] = text.count("@tpu_custom_call")
    return out


def precision_probe(seed: int, *, interpret: bool = False) -> dict:
    """Max |error| of an fp32 (8, 3072) @ (3072, 256) product computed in
    one Pallas kernel, against the float64 product on the host: with the
    kernels' ``mxu_dot`` (``highest``) and with a plain ``jnp.dot`` at
    Mosaic's default precision (``default``).  3072 is the paper
    geometry's morphed row width."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from repro.kernels.aug_gemm import mxu_dot

    g = np.random.default_rng(seed)
    a = g.standard_normal((8, 3072)).astype(np.float32)
    b = (g.standard_normal((3072, 256)) / np.sqrt(3072)).astype(np.float32)
    want = a.astype(np.float64) @ b.astype(np.float64)

    def max_err(dot) -> float:
        def kernel(a_ref, b_ref, o_ref):
            o_ref[...] = dot(a_ref[...], b_ref[...])

        got = pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct(want.shape, jnp.float32),
            interpret=interpret,
        )(a, b)
        return float(np.max(np.abs(np.asarray(got, np.float64) - want)))

    return {
        "highest": max_err(mxu_dot),
        "default": max_err(
            lambda x, y: jnp.dot(x, y, preferred_element_type=jnp.float32)
        ),
        "max_want": float(np.max(np.abs(want))),
    }


def serve_and_check(flags: list[str], *, requests: int, clients: int,
                    batch: int, trace: str, seed: int) -> dict:
    """Build the front door from serve.py ``flags``, serve two fleets in this
    process, and check exactly-once delivery and every result against the
    float32 convolution reference.  Raises on any failure; returns what was
    measured."""
    import jax.numpy as jnp

    from repro.launch.serve import parse_args
    from repro.launch.server import (
        build_front, close_front, developer_kernels, make_server,
    )

    args = parse_args(["--mode", "serve", "--seed", str(seed), *flags])
    t0 = time.perf_counter()
    front = build_front(args)
    setup_s = time.perf_counter() - t0
    engine = front.engine
    registry = engine.registry
    geom = registry.geom

    async def serve():
        server = make_server(front, args)
        await server.start()
        runs = []
        try:
            for fleet_id in ("warm", "timed"):
                fleet = RecordingFleet(FleetConfig(
                    port=server.port, requests=requests, clients=clients,
                    tenants=args.tenants, batch=batch, channels=geom.alpha,
                    image_size=geom.m, trace=trace, seed=seed,
                    # No hedges: a re-send is not what is being checked.
                    timeout_ms=600_000.0, attempt_timeout_ms=600_000.0,
                    max_attempts=1, fleet_id=fleet_id,
                ))
                t = time.perf_counter()
                report = await fleet.run()
                runs.append((fleet, report, time.perf_counter() - t))
        finally:
            lost = await server.drain_and_stop(timeout=120.0)
        return runs, lost

    runs, lost = asyncio.run(serve())
    stats = engine.stats
    _require(close_front(front, lost) == 0,
             f"drain lost {lost} rids or the flusher died")
    _require(stats.shed_requests == 0, f"{stats.shed_requests} sheds")
    _require(stats.flush_failures == 0,
             f"{stats.flush_failures} failed flushes")

    kernels = {
        f"tenant-{i}": k
        for i, k in enumerate(developer_kernels(geom, args.tenants, seed))
    }
    conv = _conv_reference(geom)
    max_err, images = 0.0, 0
    for fleet, report, _ in runs:
        report.assert_exactly_once()
        _require(report.counts() == {"ok": requests},
                 f"fleet {fleet.cfg.fleet_id}: {report.counts()}")
        for rid, req in fleet.sent.items():
            perm = registry.session(req.tenant_id).provider._perm
            want = np.asarray(conv(
                jnp.asarray(req.payload), jnp.asarray(kernels[req.tenant_id])
            ))[:, perm]
            got = fleet.served[rid]
            _require(got.shape == want.shape,
                     f"{rid}: served {got.shape}, expected {want.shape}")
            max_err = max(max_err, float(np.max(np.abs(got - want))))
            images += got.shape[0]
    _require(max_err <= TOL,
             f"max |served - reference| = {max_err:.3e} > {TOL:g}")
    timed_s = runs[-1][2]
    return {
        "backend": engine.backend,
        "setup_s": setup_s,
        "max_err": max_err,
        "images_checked": images,
        "custom_calls": _custom_calls_per_step(engine, stats.bucket_shapes),
        "images_per_s": requests * batch / timed_s,
        "timed_s": timed_s,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the kernels and the fleets' images")
    args = ap.parse_args(argv)
    enable_compile_cache()

    import jax

    from repro.kernels.dispatch import resolve_backend

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform} devices", file=sys.stderr)
        return 1
    if resolve_backend(None) != "pallas":
        print(f"kernel backend resolves to {resolve_backend(None)!r}, not "
              f"'pallas'", file=sys.stderr)
        return 1
    print(f"device: {dev.device_kind} x{len(devices)}", flush=True)

    p = precision_probe(args.seed)
    print(f"precision: fp32 Pallas product, max |err| vs float64 = "
          f"{p['highest']:.3e} via mxu_dot (HIGHEST), {p['default']:.3e} "
          f"via jnp.dot at Mosaic's default (max |want| {p['max_want']:.2f})",
          flush=True)
    _require(p["highest"] <= TOL,
             f"mxu_dot max |err| {p['highest']:.3e} > {TOL:g}")

    r = serve_and_check(PAPER_FLAGS, requests=16, clients=4, batch=8,
                        trace="uniform:40", seed=args.seed)
    calls = r["custom_calls"]
    _require(bool(calls) and all(n == 2 for n in calls.values()),
             f"tpu_custom_calls per served step: {calls} (expected 2 each)")
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    print(f"set-up: {r['setup_s']:.1f} s (register 4 tenants at "
          f"3072x65536, warmup compile)")
    print("fleets: 2 x 16/16 ok, exactly once, 0 sheds, 0 failed flushes, "
          "0 lost in the drain")
    print(f"reference: max |served - float32 conv| = {r['max_err']:.3e} "
          f"(tol {TOL:g}) over {r['images_checked']} images")
    print(f"pallas: tpu_custom_call per served step (G, B): {calls}")
    print(f"peak device bytes in use: {peak}")
    print(f"informative, not a benchmark: {r['images_per_s']:.1f} images/s "
          f"client-side ({r['timed_s']:.3f} s for the timed fleet)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
