"""Pallas TPU kernel: RWKV-6 chunked linear-attention scan.

The XLA path (models/blocks._wkv_chunked) streams the intra-chunk decay
tensor through HBM; this kernel keeps *everything* per-chunk — the (L, D)
r/k/v/decay blocks, the (L, L, D) pairwise-decay tensor and the (D, D)
running state — **resident in VMEM**, so HBM traffic is exactly the
input/output streams.  This is the TPU-native form of the official CUDA wkv
kernel (DESIGN.md §5: hardware adaptation, and §Perf H3's logical extreme).

Grid: (B*H, T/L) with the time axis sequential; the state lives in a VMEM
scratch that persists across sequential grid steps (standard Pallas-TPU
accumulator pattern).  The final state is written on the last step.

Exactness: identical math to the oracle (log-space pairwise differences, all
exponents <= 0); validated against kernels/ref.wkv6_ref in tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref, o_ref, sf_ref,
            s_ref, *, n_t: int, L: int, D: int):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _():
        s_ref[...] = s0_ref[0]

    r = r_ref[0].astype(jnp.float32)      # (L, D)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)      # (D,)
    s = s_ref[...]                        # (D, D) persistent

    clw = jnp.cumsum(lw, axis=0)
    clw_prev = clw - lw

    # state contribution
    out = (r * jnp.exp(clw_prev)) @ s                     # (L, D)
    # intra-chunk (decay tensor lives only in VMEM/registers)
    diff = clw_prev[:, None, :] - clw[None, :, :]          # (L, L, D)
    tri = jnp.tril(jnp.ones((L, L), jnp.bool_), k=-1)
    dec = jnp.exp(jnp.where(tri[:, :, None], diff, -jnp.inf))
    A = jnp.einsum("td,sd,tsd->ts", r, k, dec)
    out = out + A @ v
    # bonus
    out = out + jnp.sum(r * u[None, :] * k, axis=-1, keepdims=True) * v
    o_ref[0] = out.astype(o_ref.dtype)

    # state update
    last = clw[-1]
    s_new = jnp.exp(last)[:, None] * s + (k * jnp.exp(last[None, :] - clw)).T @ v
    s_ref[...] = s_new

    @pl.when(t == n_t - 1)
    def _():
        sf_ref[0] = s_new.astype(sf_ref.dtype)


def wkv6_chunked(
    r: jax.Array, k: jax.Array, v: jax.Array, logw: jax.Array,
    u: jax.Array, s0: jax.Array, *, chunk: int = 32, interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """r/k/v/logw: (BH, T, D); u: (BH, D); s0: (BH, D, D).

    Returns (out (BH, T, D), s_final (BH, D, D)).  T must divide by chunk.
    """
    BH, T, D = r.shape
    L = min(chunk, T)
    assert T % L == 0, (T, L)
    n_t = T // L

    seq = pl.BlockSpec((1, L, D), lambda bh, t: (bh, t, 0))
    vec = pl.BlockSpec((1, D), lambda bh, t: (bh, 0))
    mat = pl.BlockSpec((1, D, D), lambda bh, t: (bh, 0, 0))

    out, s_fin = pl.pallas_call(
        functools.partial(_kernel, n_t=n_t, L=L, D=D),
        grid=(BH, n_t),
        in_specs=[seq, seq, seq, seq, vec, mat],
        out_specs=[seq, mat],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), r.dtype),
            jax.ShapeDtypeStruct((BH, D, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
    )(r, k, v, logw, u, s0)
    return out, s_fin
