"""Pallas TPU kernel: the developer-side Aug-Conv GEMM  ``F' = T @ C^{ac}``.

This is the dense matmul the developer runs every forward step after MoLe
replaces the first conv layer (paper §3.3 / eq. 5): morphed rows
``T (B, alpha m^2)`` against the fused matrix ``C^{ac} (alpha m^2, beta n^2)``.
The provider-side morph runs through it too (``ops.morph_rows``), on rows cut
to the width of the secret core.

TPU mapping: classic three-level tiling, MXU-aligned blocks, fp32 VMEM
accumulator, contraction axis innermost (sequential) so each output tile is
written exactly once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def mxu_dot(a, b):
    """One MXU tile product, accumulated in fp32.

    fp32 operands contract at full fp32 precision (``HIGHEST``): left at
    the default, Mosaic may round them to bf16 on the MXU, and delivered
    features must match a float32 convolution (paper eq. 5).  Mosaic
    refuses that precision for bf16 operands, whose products are exact in
    the fp32 accumulator anyway.
    """
    precision = lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    return jnp.dot(
        a, b, preferred_element_type=jnp.float32, precision=precision
    )


def _kernel(t_ref, c_ref, o_ref, acc_ref, *, n_kk: int):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += mxu_dot(t_ref[...], c_ref[...])

    @pl.when(kk == n_kk - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def aug_gemm(
    t: jax.Array,      # (B, K) morphed rows
    c_ac: jax.Array,   # (K, N) fused Aug-Conv matrix
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    interpret: bool,
) -> jax.Array:
    B, K = t.shape
    K2, N = c_ac.shape
    assert K == K2, (t.shape, c_ac.shape)
    bm, bn, bk = min(bm, B), min(bn, N), min(bk, K)
    assert B % bm == 0 and N % bn == 0 and K % bk == 0, (B, bm, N, bn, K, bk)
    n_kk = K // bk

    return pl.pallas_call(
        functools.partial(_kernel, n_kk=n_kk),
        grid=(B // bm, N // bn, n_kk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, N), t.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )(t, c_ac)
