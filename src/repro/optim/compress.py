"""Gradient compression for cross-pod data parallelism.

Two pieces:

  * ``quantize_int8 / dequantize_int8`` — per-leaf symmetric int8 with an
    fp32 scale; ``ErrorFeedback`` keeps the residual so compression error
    accumulates into later steps instead of being lost (1-bit-Adam-style
    convergence argument; verified in tests/test_compression.py).

  * ``compressed_psum`` — a shard_map implementation of the quantized
    all-reduce over a chosen mesh axis (the "pod" axis for cross-pod DP):
    quantize locally -> int8 all-gather over the axis (8x less traffic than an
    fp32 ring all-reduce would move) -> dequantize + sum locally.  This is the
    collective the production config would run for pod-boundary gradient
    reduction; in-pod reduction stays full-precision.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def quantize_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    scale = jnp.max(jnp.abs(x.astype(jnp.float32))) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


@dataclasses.dataclass
class ErrorFeedback:
    """Residual accumulator: compress(g + e); e' = (g + e) - decompressed."""

    @staticmethod
    def init(grads: Any) -> Any:
        return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)

    @staticmethod
    def compress(grads: Any, residual: Any) -> tuple[Any, Any]:
        def one(g, e):
            target = g.astype(jnp.float32) + e
            q, s = quantize_int8(target)
            deq = dequantize_int8(q, s)
            return deq, target - deq

        flat_g, tdef = jax.tree.flatten(grads)
        flat_e = tdef.flatten_up_to(residual)
        outs = [one(g, e) for g, e in zip(flat_g, flat_e)]
        return (
            tdef.unflatten([o[0] for o in outs]),
            tdef.unflatten([o[1] for o in outs]),
        )


def compressed_psum(x: jax.Array, axis_name: str, mesh) -> jax.Array:
    """Quantized all-reduce over ``axis_name`` via shard_map (int8 traffic)."""

    def inner(xs):
        q, s = quantize_int8(xs)
        qs = jax.lax.all_gather(q, axis_name)          # int8 over the wire
        ss = jax.lax.all_gather(s, axis_name)
        return jnp.sum(
            qs.astype(jnp.float32) * ss.reshape(-1, *([1] * xs.ndim)), axis=0
        )

    spec = P(*([None] * x.ndim))
    # check_vma=False: the all-gather+sum makes the result replicated over
    # ``axis_name`` but the variance checker cannot infer that.
    return jax.shard_map(
        inner, mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False
    )(x)
