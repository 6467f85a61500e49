"""Pallas TPU kernels: slot-indexed grouped GEMMs for the delivery engine.

The engine's microbatch carries a ``(G,)`` vector of *slot indices* into the
registry's stacked per-tenant secrets (``cores (S, q, q)``, ``augs
(S, K, N)``).  The batched GEMM in ``aug_gemm.py`` (vmapped over groups)
needs the per-group secrets materialized as ``(G, ...)`` arrays first — an
HBM gather copy that ROADMAP measured as the difference between 0.8x and
4.9x vs per-request delivery at 16 tenants whenever ``gidx != arange(S)``.

These kernels make the hot path **gather-free**: the slot-index vector is
scalar-prefetched into SMEM (``pltpu.PrefetchScalarGridSpec``), and each
grid instance's ``index_map`` reads its group's slot out of it to DMA the
tenant's secret tile **directly from the stacked array** — no ``(G, ...)``
copy ever exists.  Out-of-order, duplicate, and partial-table index vectors
all cost the same as the identity; monotone indices (the queue slot-sorts
microbatches) additionally let Mosaic reuse a resident tile when adjacent
groups share a slot.

``grouped_aug_gemm`` serves both delivery GEMMs: Aug-Conv on ``(G, B, K)``
rows, and the morph on rows cut to the core width, ``(G, B * kappa, q)``
(``ops.morph_rows_grouped``).  Its grid mirrors the unbatched kernel with a
leading group dimension: (G, B/bm, N/bn, K/bk).

The contraction axis stays innermost ("arbitrary"), accumulated in an fp32
VMEM scratch; the group axis is "arbitrary" too because its block mapping
depends on the prefetched scalars.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .aug_gemm import mxu_dot

__all__ = [
    "grouped_aug_gemm",
    "grouped_row_gemm",
]


def _aug_kernel(gidx_ref, t_ref, c_ref, o_ref, acc_ref, *, n_kk: int):
    del gidx_ref
    kk = pl.program_id(3)

    @pl.when(kk == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += mxu_dot(t_ref[0], c_ref[0])

    @pl.when(kk == n_kk - 1)
    def _():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def grouped_aug_gemm(
    t: jax.Array,        # (G, B, K) morphed rows
    gidx: jax.Array,     # (G,) int32 slot index per group
    c_acs: jax.Array,    # (S, K, N) stacked per-slot Aug-Conv matrices
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    interpret: bool,
    name: str | None = None,
) -> jax.Array:
    """Per-group Aug-Conv forward ``t[g] @ c_acs[gidx[g]]``, secrets in place.

    The grouped twin of ``aug_gemm.aug_gemm`` — this is the GEMM whose
    ``(G, K, N)`` weight gather dominated the non-identity delivery path.
    ``name`` names the kernel, and with it the operation in a device trace,
    whatever jitted wrapper calls it.
    """
    G, B, K = t.shape
    N = c_acs.shape[-1]
    assert c_acs.shape[1] == K, (t.shape, c_acs.shape)
    bm, bn, bk = min(bm, B), min(bn, N), min(bk, K)
    assert B % bm == 0 and N % bn == 0 and K % bk == 0, (B, bm, N, bn, K, bk)
    n_kk = K // bk

    grid = (G, B // bm, N // bn, n_kk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, bm, bk), lambda g, i, j, kk, gidx_ref: (g, i, kk)
            ),
            pl.BlockSpec(
                (1, bk, bn), lambda g, i, j, kk, gidx_ref: (gidx_ref[g], kk, j)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, bm, bn), lambda g, i, j, kk, gidx_ref: (g, i, j)
        ),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_aug_kernel, n_kk=n_kk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((G, B, N), t.dtype),
        interpret=interpret,
        name=name,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "parallel", "parallel", "arbitrary")
        ),
    )(gidx, t, c_acs)


def grouped_row_gemm(
    h: jax.Array,        # (R, K) one decode row per group
    gidx: jax.Array,     # (R,) int32 slot index per row
    tables: jax.Array,   # (S, K, N) stacked per-slot matrices (e.g. LM heads)
    *,
    bn: int = 128,
    bk: int = 512,
    interpret: bool,
) -> jax.Array:
    """Decode-shaped grouped GEMM: ``h[r] @ tables[gidx[r]]`` -> (R, N).

    Batched cross-tenant decode is a ``(G, d)``-row grouped GEMM — G groups
    of exactly one row each — so this is :func:`grouped_aug_gemm` at
    ``B = bm = 1``: the scalar-prefetched index_map still DMAs each row's
    slot matrix straight out of the stacked array, and the 1-row block is
    padded up to the fp32 (8, 128) min tile by Mosaic.  The ~8x row-pad
    waste is noise next to the gather it avoids (each slot table is
    ``K x N``, the row is ``K``).
    """
    R, K = h.shape
    assert gidx.shape == (R,), (h.shape, gidx.shape)
    out = grouped_aug_gemm(
        h[:, None, :], gidx, tables, bm=1, bn=bn, bk=bk, interpret=interpret
    )
    return out[:, 0, :]
