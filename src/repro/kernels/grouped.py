"""Pallas TPU kernel: the slot-indexed grouped GEMM of the delivery engine.

``tiled_group_gemm`` multiplies rows sorted by group, padded to whole
``bm``-row tiles, each tile by its group's matrix read **in place** from a
stacked ``(S, K, N)`` array: the tile-to-group vector is scalar-prefetched
into SMEM (``pltpu.PrefetchScalarGridSpec``), and each grid instance's
``index_map`` reads its tile's group out of it to DMA the matrix's blocks
straight from the stack, so no ``(G, K, N)`` per-group copy ever exists.
Out-of-order, duplicate, and partial-table group vectors all cost the same.
A tile's group matrix is read once per tile; tiles past the used count hold
no rows: they skip the product, and their weight index stays on one block,
so they stream no matrix.

It serves every grouped product:

* the rows step's morph and Aug-Conv (``ops.morph_rows_grouped``, kernel
  ``mole_morph_grouped``, and ``ops.aug_conv_forward_grouped``,
  ``mole_aug_conv_grouped``): each group of a microbatch is whole row
  tiles already, and its padding groups are the unused tiles, so their
  slots are not read;
* the dropless MoE (tokens grouped by expert, ``mole_expert_gmm``) and the
  batched decode's Aug-head (rows grouped by tenant slot,
  ``mole_lm_head_grouped``).

The contraction axis stays innermost ("arbitrary"), accumulated in an fp32
VMEM scratch; the tile axis is "arbitrary" too because its block mapping
depends on the prefetched scalars.  A tile re-streams its group's matrix
even where the previous tile had the same group.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .aug_gemm import mxu_dot

__all__ = ["tiled_group_gemm"]


def _tiled_kernel(tg_ref, nu_ref, x_ref, w_ref, o_ref, acc_ref, *, n_kk: int):
    del tg_ref
    i, kk = pl.program_id(0), pl.program_id(2)

    @pl.when(kk == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i < nu_ref[0])
    def _():
        acc_ref[...] += mxu_dot(x_ref[...], w_ref[0])

    @pl.when(kk == n_kk - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def tiled_group_gemm(
    x: jax.Array,            # (M, K) rows sorted by group, M = tiles * bm
    tile_group: jax.Array,   # (tiles,) int32 group of each row tile
    n_used: jax.Array,       # (1,) int32 tiles holding rows
    w: jax.Array,            # (G, K, N) stacked per-group matrices
    *,
    bm: int,
    bn: int,
    bk: int,
    interpret: bool,
    name: str,
    out_dtype=None,
) -> jax.Array:
    """``x[tile] @ w[tile_group[tile]]`` for each ``bm``-row tile -> (M, N),
    in ``out_dtype`` (default ``x.dtype``), accumulated in float32.

    Both index vectors are scalar-prefetched: each tile DMAs its group's
    weight blocks straight from the stack.  Tiles past ``n_used`` hold no
    rows: they skip the product (their output is zeros), and their weight
    index stays on one block, so they read no weights.  Callers give the
    unused tiles the last used tile's group, so nothing new is fetched.
    """
    M, K = x.shape
    N = w.shape[-1]
    tiles = tile_group.shape[0]
    assert w.shape[1] == K and M == tiles * bm, (x.shape, w.shape, tiles, bm)
    assert N % bn == 0 and K % bk == 0, (N, bn, K, bk)
    n_kk = K // bk

    def w_map(i, j, kk, tg, nu):
        used = i < nu[0]
        return (tg[i], jnp.where(used, kk, 0), jnp.where(used, j, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(tiles, N // bn, n_kk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk, tg, nu: (i, kk)),
            pl.BlockSpec((1, bk, bn), w_map),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk, tg, nu: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_tiled_kernel, n_kk=n_kk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype or x.dtype),
        interpret=interpret,
        name=name,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")
        ),
    )(tile_group, n_used, x, w)
