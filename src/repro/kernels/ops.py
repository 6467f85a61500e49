"""jit'd public wrappers for the Pallas kernels, with backend dispatch.

Backend selection (see :mod:`repro.kernels.dispatch`):

  * ``pallas``     — compiled Pallas (real TPU),
  * ``interpret``  — Pallas interpret mode (CPU kernel validation),
  * ``jnp``        — pure-jnp reference (``ref.py``; the CPU path).

``backend=None`` resolves via ``REPRO_KERNEL_BACKEND`` / hardware auto-detect.
Callers only pass ``backend`` through: this module alone decides what runs,
one implementation per backend and entry point, the same program for every
slot-index layout.
On the two Pallas backends every GEMM entry point runs its kernel, never the
reference, at every width: the row axis is zero-padded up to the kernels'
row tile (and the padding sliced off the result), and a weight axis off the
128-lane grid is one whole-axis block.  The grouped delivery GEMMs pad their
rows the same way on ``jnp``: XLA:CPU fuses a one-row product in the
reference's scan into a loop that sums in another order than the unbatched
product, so an unpadded one-row microbatch would differ from per-request
delivery in the last bit.

The morph ``x @ blockdiag(core, ..., core)`` (paper eq. 2-4) runs as the
same GEMM kernel as Aug-Conv: cut each row of ``kappa * q`` features into
``kappa`` rows of ``q`` and multiply them by the ``q x q`` core.

The ``*_grouped`` entry points are the delivery-engine hot path: a leading
*group* axis carries per-tenant secrets read in place from a stacked slot
table (one morph core / one Aug-Conv matrix per slot).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import ref
from .aug_gemm import aug_gemm
from .dispatch import pallas_interpret, resolve_backend
from .grouped import tiled_group_gemm

__all__ = [
    "morph_rows",
    "aug_conv_forward",
    "morph_rows_grouped",
    "aug_conv_forward_grouped",
    "token_morph_grouped",
    "aug_embed_grouped",
    "aug_embed_rows_grouped",
    "lm_head_rows_grouped",
    "expert_ffn",
]

# Mosaic's block rule: a block's last axis is a multiple of the 128-lane
# vreg width or spans the whole array axis; its second-to-last axis is a
# multiple of the 8-row sublane tile or spans the whole axis.
_LANE, _SUBLANE = 128, 8


def _lane_tile(dim: int, cap: int) -> int:
    """Block extent along a weight axis of ``dim`` elements: the largest
    multiple of 128 up to ``cap`` dividing ``dim``, else the whole axis."""
    for t in range(min(cap, dim) // _LANE * _LANE, 0, -_LANE):
        if dim % t == 0:
            return t
    return dim


def _gemm_tiles(K: int, N: int) -> tuple[int, int]:
    """(bn, bk) for a ``(rows, K) @ (K, N)`` GEMM: both weight axes are
    whole array axes, so every width has a legal block."""
    return _lane_tile(N, 128), _lane_tile(K, 512)


def _pad_rows(x: jax.Array, axis: int) -> tuple[jax.Array, int, int]:
    """Zero-pad the row axis up to the kernels' row tile.

    Returns ``(padded, rows, bm)``: rows round up to the 8-row sublane tile,
    and past 128 to a multiple of the 128-row block.  Padding rows are zero,
    so they produce zero rows that the caller slices off.
    """
    rows = x.shape[axis]
    bm = min(128, -(-rows // _SUBLANE) * _SUBLANE)
    padded = -(-rows // bm) * bm
    if padded != rows:
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, padded - rows)
        x = jnp.pad(x, widths)
    return x, rows, bm


def morph_rows(
    x: jax.Array, core: jax.Array, kappa: int, backend: str | None = None
) -> jax.Array:
    """Provider-side morphing: x (R, kappa*q) @ blockdiag(core)."""
    return _morph_rows(x, core, int(kappa), resolve_backend(backend))


@partial(jax.jit, static_argnames=("kappa", "backend"))
def _morph_rows(x, core, kappa, backend):
    if backend == "jnp":
        return ref.block_diag_matmul_ref(x, core, kappa)
    q = core.shape[0]
    return _gemm(x.reshape(-1, q), core, backend).reshape(x.shape)


def aug_conv_forward(
    t: jax.Array, c_ac: jax.Array, backend: str | None = None
) -> jax.Array:
    """Developer-side Aug-Conv layer: t (B, K) @ c_ac (K, N)."""
    return _aug_conv_forward(t, c_ac, resolve_backend(backend))


@partial(jax.jit, static_argnames=("backend",))
def _aug_conv_forward(t, c_ac, backend):
    if backend == "jnp":
        return ref.aug_gemm_ref(t, c_ac)
    return _gemm(t, c_ac, backend)


def _gemm(t, w, backend):
    """``t (B, K) @ w (K, N)`` through the Pallas GEMM kernel."""
    bn, bk = _gemm_tiles(*w.shape)
    t, B, bm = _pad_rows(t, 0)
    return aug_gemm(
        t, w, bm=bm, bn=bn, bk=bk, interpret=pallas_interpret(backend)
    )[:B]


def _safe_gidx(gidx: jax.Array, n_slots: int) -> jax.Array:
    """Clamp slot indices into the stacked-secret range.

    Padding groups at the tail of a microbatch may carry an index past the
    slot table (the queue can only see the group bucket, not the registry
    capacity); XLA's gather clamps out-of-range indices silently, but the
    Pallas index_maps DMA whatever block they are told to — so the grouped
    entry points clamp once here.  Padding rows are zero, so the result is
    zeros regardless of whose secret they hit.
    """
    return jnp.clip(gidx.astype(jnp.int32), 0, n_slots - 1)


def morph_rows_grouped(
    x: jax.Array, gidx: jax.Array, cores: jax.Array, kappa: int,
    backend: str | None = None, n_real: jax.Array | None = None,
) -> jax.Array:
    """Slot-indexed morphing: x (G, B, kappa*q), gidx (G,), cores (S, q, q).

    The gather-free delivery hot path: per-group secrets are read **in
    place** from the stacked slot table — on Pallas backends the scalar-
    prefetched index_map DMAs slot ``gidx[g]``'s core tile directly, and the
    jnp reference dynamic-slices one core per ``lax.scan`` step — so no
    ``(G, q, q)`` copy is ever materialized, for *any* index vector
    (out-of-order, duplicate, partial-table, or the identity).  ``n_real``
    (1,) int32, if given, counts the live groups: the groups past it are
    padding, their output is zeros, and the Pallas backends do not read
    their slots.
    """
    return _morph_rows_grouped(
        x, gidx, cores, int(kappa), resolve_backend(backend), n_real
    )


@partial(jax.jit, static_argnames=("kappa", "backend"))
def _morph_rows_grouped(x, gidx, cores, kappa, backend, n_real=None):
    gidx = _safe_gidx(gidx, cores.shape[0])
    if backend != "jnp":
        G, q = x.shape[0], cores.shape[-1]
        return _live_groups_gemm(
            x.reshape(G, -1, q), gidx, n_real, cores, backend,
            "mole_morph_grouped",
        ).reshape(x.shape)
    x, B, _ = _pad_rows(x, 1)
    out = ref.block_diag_matmul_grouped_ref(x, gidx, cores, kappa)[:, :B]
    return _zero_padding_groups(out, n_real)


def aug_conv_forward_grouped(
    t: jax.Array, gidx: jax.Array, c_acs: jax.Array,
    backend: str | None = None, n_real: jax.Array | None = None,
) -> jax.Array:
    """Slot-indexed Aug-Conv forward: t (G, B, K), gidx (G,), c_acs (S, K, N).

    This is the GEMM whose per-microbatch ``(G, K, N)`` weight gather was
    the non-identity delivery cost (ROADMAP: 0.8x vs 4.9x at 16 tenants);
    here the slot table is read in place on every backend.  ``n_real`` as
    in :func:`morph_rows_grouped`: a padding group reads no slot.
    """
    return _aug_conv_forward_grouped(
        t, gidx, c_acs, resolve_backend(backend), n_real
    )


@partial(jax.jit, static_argnames=("backend",))
def _aug_conv_forward_grouped(t, gidx, c_acs, backend, n_real=None):
    gidx = _safe_gidx(gidx, c_acs.shape[0])
    if backend != "jnp":
        return _live_groups_gemm(
            t, gidx, n_real, c_acs, backend, "mole_aug_conv_grouped"
        )
    t, B, _ = _pad_rows(t, 1)
    out = ref.aug_gemm_grouped_ref(t, gidx, c_acs)[:, :B]
    return _zero_padding_groups(out, n_real)


def _zero_padding_groups(out, n_real):
    """``out`` (G, ...) with the groups from ``n_real[0]`` on set to zero."""
    if n_real is None:
        return out
    live = jnp.arange(out.shape[0]) < n_real[0]
    return jnp.where(live.reshape((-1,) + (1,) * (out.ndim - 1)), out, 0)


def _live_groups_gemm(t, gidx, n_real, ws, backend, name):
    """``t[g] (B, K) @ ws[gidx[g]] (K, N)`` for the live groups, through
    the tiled kernel named ``name``, the stacked weights read in place.

    The microbatch is already a tile layout: each group's rows, padded to
    the row tile, are whole ``bm``-row tiles, so ``(G, B, K)`` is read as
    ``(G * B, K)`` with no copy.  The groups from ``n_real[0]`` on (all
    live when None) are padding: their tiles are past the used count, so
    their output is zeros, and they name the last live group's slot, so
    the kernel streams no matrix for them.
    """
    G = t.shape[0]
    t, B, bm = _pad_rows(t, 1)
    per = t.shape[1] // bm                      # row tiles a group
    if n_real is None:
        n_real = jnp.full((1,), G, jnp.int32)
    n_real = n_real.astype(jnp.int32)
    last = gidx[jnp.clip(n_real[0] - 1, 0, G - 1)]
    slots = jnp.where(jnp.arange(G) < n_real[0], gidx, last)
    tiles = {"tile_group": jnp.repeat(slots, per), "n_used": n_real * per}
    out = _tiled_products(t.reshape(G * t.shape[1], -1), tiles, ws, bm,
                          backend, name)
    return out.reshape(G, t.shape[1], -1)[:, :B]


def token_morph_grouped(
    tokens: jax.Array, gidx: jax.Array, perms: jax.Array,
    backend: str | None = None,
) -> jax.Array:
    """Slot-indexed token morphing: tokens (G, B, L), gidx (G,), perms (S, V).

    The LM twin of :func:`morph_rows_grouped`: one gather reads each
    group's tokens through its slot's permutation in place from the stacked
    ``(S, V)`` table, so the ``(G, V)`` per-microbatch permutation copy is
    never materialized.  Discrete morphing is a gather — memory-bound, no
    MACs — so every backend runs the XLA gather (hand-rolling a TPU gather
    would only re-derive what Mosaic emits); ``backend`` is still resolved
    and validated so call sites stay uniform with the GEMM entry points.
    """
    resolve_backend(backend)
    return _token_morph_grouped(tokens, gidx, perms)


@jax.jit
def _token_morph_grouped(tokens, gidx, perms):
    gidx = _safe_gidx(gidx, perms.shape[0])
    return perms[gidx[:, None, None], tokens]


def aug_embed_grouped(
    tokens: jax.Array, gidx: jax.Array, tables: jax.Array,
    backend: str | None = None,
) -> jax.Array:
    """Slot-indexed Aug-Embedding: morphed tokens (G, B, L) gathered from the
    stacked ``(S, V, d)`` tables via gidx (G,) -> (G, B, L, d), without the
    ``(G, V, d)`` per-microbatch table copy (the largest secret stack).
    Like :func:`token_morph_grouped`, one gather on every backend — "gather
    stays a gather: zero runtime overhead" (``core.lm``)."""
    resolve_backend(backend)
    return _aug_embed_grouped(tokens, gidx, tables)


@jax.jit
def _aug_embed_grouped(tokens, gidx, tables):
    gidx = _safe_gidx(gidx, tables.shape[0])
    return tables[gidx[:, None, None], tokens]


def aug_embed_rows_grouped(
    tokens: jax.Array, gidx: jax.Array, tables: jax.Array,
    backend: str | None = None,
) -> jax.Array:
    """Per-row slot-indexed AugE gather — the batched-decode embedding step.

    tokens (R,) int (one *morphed* token per decode row), gidx (R,),
    tables (S, V, d) -> (R, d).  A gather stays a gather: like
    :func:`token_morph_grouped`, every backend routes to the XLA
    formulation (no MACs to win back on the MXU), one gather of the R rows
    read in place from the stacked tables.
    """
    resolve_backend(backend)
    return _aug_embed_rows_grouped(tokens, gidx, tables)


@jax.jit
def _aug_embed_rows_grouped(tokens, gidx, tables):
    gidx = _safe_gidx(gidx, tables.shape[0])
    return ref.aug_embed_rows_grouped_ref(tokens, gidx, tables)


def lm_head_rows_grouped(
    h: jax.Array, gidx: jax.Array, heads: jax.Array,
    backend: str | None = None, keep: jax.Array | None = None,
) -> jax.Array:
    """Slot-indexed per-row LM-head GEMM — the batched-decode logits step.

    h (R, d) final hidden states (one per decode row), gidx (R,), heads
    (S, d, V) fused per-slot Aug-heads -> (R, V) morphed-order logits.
    Decode *is* a (R, d)-row grouped GEMM against the stacked heads: Pallas
    backends sort the rows by slot and run
    :func:`repro.kernels.grouped.tiled_group_gemm` (the kernel
    ``mole_lm_head_grouped``), so each slot's head is read once per tile of
    its rows, in place, and a slot no row uses is not read; the jnp
    backend mirrors ``models.stack.lm_head``'s dtype semantics exactly
    (contraction in ``h.dtype``) so batched decode emits bit-identical
    logits.  ``keep`` (R,) bool, if given, spares the
    Pallas backends the other rows (their logits are zeros there), so a
    slot only such rows use is not read.
    """
    return _lm_head_rows_grouped(h, gidx, heads, resolve_backend(backend),
                                 keep=keep)


@partial(jax.jit, static_argnames=("backend",))
def _lm_head_rows_grouped(h, gidx, heads, backend, keep=None):
    gidx = _safe_gidx(gidx, heads.shape[0])
    if backend != "jnp":
        # Rows grouped by slot: each slot's head is read once per tile of
        # its rows, not once per row.
        bm = min(128, -(-h.shape[0] // _SUBLANE) * _SUBLANE)
        out = _tiled_gemm(h, gidx, heads, bm, backend, "mole_lm_head_grouped",
                          out_dtype=jnp.float32, keep=keep)
        return out.astype(h.dtype)
    return ref.lm_head_rows_grouped_ref(h, gidx, heads)


# -- ragged groups: rows sorted by group, padded to whole tiles -------------------
def _block_tiles(K: int, N: int, itemsize: int,
                 budget: int = 2 * 2 ** 20) -> tuple[int, int]:
    """(bn, bk) of a weight block: the largest ``bk x bn`` block within
    ``budget`` bytes, each axis a multiple of 128 dividing it or the whole
    axis; ties go to the longer contraction."""
    def sizes(dim):
        return [t for t in range(_LANE, dim + 1, _LANE) if dim % t == 0] or [dim]

    fits = [(bn * bk, bk, bn) for bn in sizes(N) for bk in sizes(K)
            if bn * bk * itemsize <= budget]
    _, bk, bn = max(fits) if fits else (0, sizes(K)[0], sizes(N)[0])
    return bn, bk


def tile_count(rows: int, n_groups: int, bm: int) -> int:
    """Tiles enough for ``rows`` rows in ``n_groups`` groups, each group
    padded to whole ``bm``-row tiles, whatever the grouping:
    ``G + (rows - G) // bm`` with ``G = min(n_groups, rows)`` bounds
    ``sum_g ceil(n_g / bm)``."""
    g = min(n_groups, rows)
    return g + (rows - g) // bm


def group_tiles(groups: jax.Array, n_groups: int, bm: int,
                keep: jax.Array | None = None) -> dict:
    """Tile layout of rows sorted by group, each group padded to whole
    ``bm``-row tiles (:func:`tile_count` of them).

    ``groups`` (P,) int: each row's group; ``keep`` (P,) bool, if given,
    leaves the other rows out: they sort last and take no tile.  Returns
    ``order`` (P,) the rows in group order, ``slot`` (P,) where each sorted
    row sits in the padded buffer (a left-out row's lies past its end),
    ``tile_group`` (tiles,) each tile's group (unused tiles repeat the last
    used tile's) and ``n_used`` (1,).
    """
    P = groups.shape[0]
    n_tiles = tile_count(P, n_groups, bm)
    groups = groups.astype(jnp.int32)
    if keep is not None:
        groups = jnp.where(keep, groups, n_groups)
    order = jnp.argsort(groups, stable=True)
    sorted_g = groups[order]
    counts = jnp.zeros((n_groups + 1,), jnp.int32).at[groups].add(1)
    starts = jnp.cumsum(counts) - counts               # first sorted row
    tiles_of = (counts[:n_groups] + bm - 1) // bm
    tile_end = jnp.cumsum(tiles_of)
    tile_start = jnp.append(tile_end - tiles_of, n_tiles)
    rank = jnp.arange(P, dtype=jnp.int32) - starts[sorted_g]
    slot = tile_start[sorted_g] * bm + rank
    n_used = tile_end[-1]
    i = jnp.arange(n_tiles, dtype=jnp.int32)
    tg = jnp.searchsorted(tile_end, jnp.minimum(i, n_used - 1), side="right")
    return {"order": order, "slot": slot, "tile_group": tg.astype(jnp.int32),
            "n_used": n_used.reshape(1).astype(jnp.int32)}


def _tiled_gemm(x, gidx, w, bm, backend, name, out_dtype=None, keep=None):
    """Rows ``x`` (P, K) in groups ``gidx`` (P,), each times its group's
    ``w[g]`` (K, N) -> (P, N) in the original row order; rows outside
    ``keep`` are not computed (zeros)."""
    P = x.shape[0]
    tiles = group_tiles(gidx, w.shape[0], bm, keep)
    buf = jnp.zeros((tile_count(P, w.shape[0], bm) * bm, x.shape[1]), x.dtype)
    buf = buf.at[tiles["slot"]].set(x[tiles["order"]], mode="drop")
    out = _tiled_products(buf, tiles, w, bm, backend, name, out_dtype)
    back = jnp.zeros((x.shape[0],), jnp.int32).at[tiles["order"]].set(
        tiles["slot"])
    return out.at[back].get(mode="fill", fill_value=0)


def _tiled_products(buf, tiles, w, bm, backend, name, out_dtype=None):
    """``buf``'s ``bm``-row tiles times their groups' matrices: the Pallas
    kernel, or on the jnp backend one einsum over the tiles' gathered
    matrices."""
    dt = out_dtype or buf.dtype
    if backend == "jnp":
        xt = buf.reshape(-1, bm, buf.shape[1])
        out = jnp.einsum("tbk,tkn->tbn", xt, w[tiles["tile_group"]].astype(
            buf.dtype), preferred_element_type=jnp.float32)
        used = jnp.arange(xt.shape[0]) < tiles["n_used"][0]
        out = jnp.where(used[:, None, None], out, 0.0)
        return out.reshape(buf.shape[0], -1).astype(dt)
    bn, bk = _block_tiles(w.shape[1], w.shape[2], w.dtype.itemsize)
    return tiled_group_gemm(
        buf, tiles["tile_group"], tiles["n_used"], w, bm=bm, bn=bn, bk=bk,
        interpret=pallas_interpret(backend), name=name, out_dtype=dt,
    )


def expert_ffn(x: jax.Array, experts: jax.Array, weights: jax.Array,
               wg: jax.Array, wu: jax.Array, wd: jax.Array, *, act: str,
               keep: jax.Array | None = None,
               backend: str | None = None) -> jax.Array:
    """Dropless routed experts: token ``t`` goes through each of its experts
    ``experts[t]`` (T, k), weighted by ``weights[t]`` (T, k), and the
    weighted outputs sum -> (T, d).  Tokens outside ``keep`` (T,) bool, if
    given, go to no expert (their output is zeros).

    The (token, expert) pairs are sorted by expert and padded to whole row
    tiles (:func:`group_tiles`); the gated FFN then runs as three grouped
    GEMMs, the Pallas kernel ``mole_expert_gmm``, which reads each chosen
    expert's matrices once per tile of its tokens and never reads an expert
    no token chose.
    """
    return _expert_ffn(x, experts, weights, wg, wu, wd, act=act,
                       backend=resolve_backend(backend), keep=keep)


@partial(jax.jit, static_argnames=("act", "backend"))
def _expert_ffn(x, experts, weights, wg, wu, wd, act, backend, keep=None):
    from ..models.layers import act_fn

    T, k = experts.shape
    E = wg.shape[0]
    P = T * k
    per = -(-2 * P // E)
    bm = min(128, max(_SUBLANE, 1 << (per - 1).bit_length()))
    tiles = group_tiles(experts.reshape(-1), E, bm,
                        None if keep is None else jnp.repeat(keep, k))
    tok = tiles["order"] // k
    buf = jnp.zeros((tile_count(P, E, bm) * bm, x.shape[1]), x.dtype)
    buf = buf.at[tiles["slot"]].set(x[tok], mode="drop")
    g = _tiled_products(buf, tiles, wg, bm, backend, "mole_expert_gmm")
    u = _tiled_products(buf, tiles, wu, bm, backend, "mole_expert_gmm")
    h = (act_fn(act)(g) * u).astype(x.dtype)
    out = _tiled_products(h, tiles, wd, bm, backend, "mole_expert_gmm")
    w = weights.reshape(-1)[tiles["order"]].astype(jnp.float32)
    y = jnp.zeros(x.shape, jnp.float32).at[tok].add(
        out.at[tiles["slot"]].get(mode="fill", fill_value=0).astype(
            jnp.float32) * w[:, None])
    return y.astype(x.dtype)
