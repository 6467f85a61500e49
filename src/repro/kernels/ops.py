"""jit'd public wrappers for the Pallas kernels, with backend dispatch.

Backend selection (see :mod:`repro.kernels.dispatch`):

  * ``pallas``     — compiled Pallas (real TPU),
  * ``interpret``  — Pallas interpret mode (CPU kernel validation),
  * ``jnp``        — pure-jnp reference (``ref.py``; the fast CPU path).

``backend=None`` resolves via ``REPRO_KERNEL_BACKEND`` / hardware auto-detect.
On the two Pallas backends every GEMM entry point runs its kernel, never the
reference, at every width: the row axis is zero-padded up to the kernels'
row tile (and the padding sliced off the result), and a weight axis off the
128-lane grid is one whole-axis block.

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
from .grouped import grouped_aug_gemm, grouped_row_gemm

__all__ = [
    "morph_rows",
    "aug_conv_forward",
    "morph_rows_batched",
    "aug_conv_forward_batched",
    "morph_rows_grouped",
    "aug_conv_forward_grouped",
    "token_morph_batched",
    "aug_embed_batched",
    "token_morph_grouped",
    "aug_embed_grouped",
    "aug_embed_rows_grouped",
    "lm_head_rows_grouped",
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


def morph_rows_batched(
    x: jax.Array, cores: jax.Array, kappa: int, backend: str | None = None
) -> jax.Array:
    """Per-group morphing: x (G, B, kappa*q) with cores (G, q, q).

    Each group carries one tenant's secret core; Pallas backends vmap the
    GEMM kernel over the group axis.
    """
    return _morph_rows_batched(x, cores, int(kappa), resolve_backend(backend))


@partial(jax.jit, static_argnames=("kappa", "backend"))
def _morph_rows_batched(x, cores, kappa, backend):
    if backend == "jnp":
        return ref.block_diag_matmul_batched_ref(x, cores, kappa)
    G, q = x.shape[0], cores.shape[-1]
    return jax.vmap(partial(_gemm, backend=backend))(
        x.reshape(G, -1, q), cores
    ).reshape(x.shape)


def aug_conv_forward_batched(
    t: jax.Array, c_acs: jax.Array, backend: str | None = None
) -> jax.Array:
    """Per-group Aug-Conv forward: t (G, B, K) @ c_acs (G, K, N) -> (G, B, N)."""
    return _aug_conv_forward_batched(t, c_acs, resolve_backend(backend))


@partial(jax.jit, static_argnames=("backend",))
def _aug_conv_forward_batched(t, c_acs, backend):
    if backend == "jnp":
        return ref.aug_gemm_batched_ref(t, c_acs)
    return jax.vmap(partial(_gemm, backend=backend))(t, c_acs)


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


def _with_arange_fast_case(gidx, n_slots, fast, general, *operands):
    """Value-level fast case for the jnp grouped fallbacks.

    When the microbatch spans the full slot table in slot order (the
    slot-sorted steady state: ``gidx == arange(S)``), the per-group secrets
    are the stacked array itself, and XLA's batched einsum reads it in place
    with full threading — measurably faster on CPU than the scan of dynamic
    slices.  The check is a ``lax.cond`` on the *values*, inside one
    compiled graph: unlike the engine's old host-side ``identity_gather``
    static flag there is nothing to re-trace when traffic shifts between
    layouts, and the Pallas backends never need it (their index maps read
    in place for any ``gidx``).  Statically skipped unless ``G == S`` —
    ``arange(G)`` cannot cover a larger table.
    """
    if gidx.shape[0] != n_slots:
        return general(*operands)
    return jax.lax.cond(
        jnp.array_equal(gidx, jnp.arange(n_slots, dtype=gidx.dtype)),
        fast, general, *operands,
    )


def morph_rows_grouped(
    x: jax.Array, gidx: jax.Array, cores: jax.Array, kappa: int,
    backend: str | None = None,
) -> jax.Array:
    """Slot-indexed morphing: x (G, B, kappa*q), gidx (G,), cores (S, q, q).

    The gather-free delivery hot path: per-group secrets are read **in
    place** from the stacked slot table — on Pallas backends the scalar-
    prefetched index_map DMAs slot ``gidx[g]``'s core tile directly, and the
    jnp reference dynamic-slices one core per ``lax.scan`` step — so no
    ``(G, q, q)`` copy is ever materialized, for *any* index vector
    (out-of-order, duplicate, partial-table, or the identity).
    """
    return _morph_rows_grouped(
        x, gidx, cores, int(kappa), resolve_backend(backend)
    )


@partial(jax.jit, static_argnames=("kappa", "backend"))
def _morph_rows_grouped(x, gidx, cores, kappa, backend):
    gidx = _safe_gidx(gidx, cores.shape[0])
    if backend != "jnp":
        G, q = x.shape[0], cores.shape[-1]
        return _grouped_gemm(
            x.reshape(G, -1, q), gidx, cores, backend, "mole_morph_grouped"
        ).reshape(x.shape)
    return _with_arange_fast_case(
        gidx, cores.shape[0],
        lambda x_, g_: ref.block_diag_matmul_batched_ref(x_, cores, kappa),
        lambda x_, g_: ref.block_diag_matmul_grouped_ref(x_, g_, cores, kappa),
        x, gidx,
    )


def aug_conv_forward_grouped(
    t: jax.Array, gidx: jax.Array, c_acs: jax.Array,
    backend: str | None = None,
) -> jax.Array:
    """Slot-indexed Aug-Conv forward: t (G, B, K), gidx (G,), c_acs (S, K, N).

    This is the GEMM whose per-microbatch ``(G, K, N)`` weight gather was
    the non-identity delivery cost (ROADMAP: 0.8x vs 4.9x at 16 tenants);
    here the slot table is read in place on every backend.
    """
    return _aug_conv_forward_grouped(t, gidx, c_acs, resolve_backend(backend))


@partial(jax.jit, static_argnames=("backend",))
def _aug_conv_forward_grouped(t, gidx, c_acs, backend):
    gidx = _safe_gidx(gidx, c_acs.shape[0])
    if backend != "jnp":
        return _grouped_gemm(t, gidx, c_acs, backend, "mole_aug_conv_grouped")
    return _with_arange_fast_case(
        gidx, c_acs.shape[0],
        lambda t_, g_: ref.aug_gemm_batched_ref(t_, c_acs),
        lambda t_, g_: ref.aug_gemm_grouped_ref(t_, g_, c_acs),
        t, gidx,
    )


def _grouped_gemm(t, gidx, ws, backend, name):
    """``t[g] (B, K) @ ws[gidx[g]] (K, N)`` through the grouped Pallas
    kernel named ``name``, the stacked weights read in place."""
    bn, bk = _gemm_tiles(*ws.shape[1:])
    t, B, bm = _pad_rows(t, 1)
    return grouped_aug_gemm(
        t, gidx, ws, bm=bm, bn=bn, bk=bk, interpret=pallas_interpret(backend),
        name=name,
    )[:, :B]


def token_morph_batched(
    tokens: jax.Array, perms: jax.Array, backend: str | None = None
) -> jax.Array:
    """Per-group token morphing: tokens (G, B, L) with perms (G, V).

    The LM delivery-engine hot path.  Discrete morphing is a dynamic gather
    — memory-bound, no MACs — so every backend routes to XLA's native gather
    (the Pallas kernels in this package exist for the GEMM-shaped paths;
    hand-rolling a TPU gather here would only re-derive what Mosaic emits).
    The ``backend`` flag is still resolved/validated so call sites stay
    uniform with the GEMM entry points.
    """
    resolve_backend(backend)
    return ref.token_morph_batched_ref(tokens, perms)


def aug_embed_batched(
    tokens: jax.Array, tables: jax.Array, backend: str | None = None
) -> jax.Array:
    """Per-group Aug-Embedding forward: morphed tokens (G, B, L) gathered
    from per-group (V, d) tables -> (G, B, L, d).

    Like :func:`token_morph_batched`, a gather on every backend — "gather
    stays a gather: zero runtime overhead" (``core.lm``).
    """
    resolve_backend(backend)
    return ref.aug_embed_batched_ref(tokens, tables)


def token_morph_grouped(
    tokens: jax.Array, gidx: jax.Array, perms: jax.Array,
    backend: str | None = None,
) -> jax.Array:
    """Slot-indexed token morphing: tokens (G, B, L), gidx (G,), perms (S, V).

    The LM twin of :func:`morph_rows_grouped`: each scan step dynamic-slices
    one slot's permutation out of the stacked ``(S, V)`` table, so the
    ``(G, V)`` per-microbatch permutation copy is never materialized.  A
    gather-of-gathers is still memory-bound with no MACs, so every backend
    routes to the XLA formulation (see :func:`token_morph_batched`).
    """
    resolve_backend(backend)
    return _token_morph_grouped(tokens, gidx, perms)


@jax.jit
def _token_morph_grouped(tokens, gidx, perms):
    gidx = _safe_gidx(gidx, perms.shape[0])
    return _with_arange_fast_case(
        gidx, perms.shape[0],
        lambda t_, g_: ref.token_morph_batched_ref(t_, perms),
        lambda t_, g_: ref.token_morph_grouped_ref(t_, g_, perms),
        tokens, gidx,
    )


def aug_embed_grouped(
    tokens: jax.Array, gidx: jax.Array, tables: jax.Array,
    backend: str | None = None,
) -> jax.Array:
    """Slot-indexed Aug-Embedding: morphed tokens (G, B, L) gathered from the
    stacked ``(S, V, d)`` tables via gidx (G,) -> (G, B, L, d), without the
    ``(G, V, d)`` per-microbatch table copy (the largest secret stack)."""
    resolve_backend(backend)
    return _aug_embed_grouped(tokens, gidx, tables)


@jax.jit
def _aug_embed_grouped(tokens, gidx, tables):
    gidx = _safe_gidx(gidx, tables.shape[0])
    return _with_arange_fast_case(
        gidx, tables.shape[0],
        lambda t_, g_: ref.aug_embed_batched_ref(t_, tables),
        lambda t_, g_: ref.aug_embed_grouped_ref(t_, g_, tables),
        tokens, gidx,
    )


def aug_embed_rows_grouped(
    tokens: jax.Array, gidx: jax.Array, tables: jax.Array,
    backend: str | None = None,
) -> jax.Array:
    """Per-row slot-indexed AugE gather — the batched-decode embedding step.

    tokens (R,) int (one *morphed* token per decode row), gidx (R,),
    tables (S, V, d) -> (R, d).  A gather stays a gather: like
    :func:`token_morph_grouped`, every backend routes to the XLA
    formulation (no MACs to win back on the MXU), with the identity
    arrangement — the continuous-batching steady state where row ``r``
    serves slot ``r`` — reading the stacked tables fully in place.
    """
    resolve_backend(backend)
    return _aug_embed_rows_grouped(tokens, gidx, tables)


@jax.jit
def _aug_embed_rows_grouped(tokens, gidx, tables):
    gidx = _safe_gidx(gidx, tables.shape[0])
    return _with_arange_fast_case(
        gidx, tables.shape[0],
        lambda t_, g_: ref.aug_embed_rows_batched_ref(t_, tables),
        lambda t_, g_: ref.aug_embed_rows_grouped_ref(t_, g_, tables),
        tokens, gidx,
    )


def lm_head_rows_grouped(
    h: jax.Array, gidx: jax.Array, heads: jax.Array,
    backend: str | None = None,
) -> jax.Array:
    """Slot-indexed per-row LM-head GEMM — the batched-decode logits step.

    h (R, d) final hidden states (one per decode row), gidx (R,), heads
    (S, d, V) fused per-slot Aug-heads -> (R, V) morphed-order logits.
    Decode *is* a (R, d)-row grouped GEMM against the stacked heads: Pallas
    backends run :func:`repro.kernels.grouped.grouped_row_gemm` (scalar-
    prefetched in-place reads, rows padded to the min tile); the jnp
    backend mirrors ``models.stack.lm_head``'s dtype semantics exactly
    (contraction in ``h.dtype``) so batched decode emits bit-identical
    logits, with the identity arrangement contracting against the stack in
    place as one batched einsum.
    """
    return _lm_head_rows_grouped(h, gidx, heads, resolve_backend(backend))


@partial(jax.jit, static_argnames=("backend",))
def _lm_head_rows_grouped(h, gidx, heads, backend):
    gidx = _safe_gidx(gidx, heads.shape[0])
    if backend != "jnp":
        bn, bk = _gemm_tiles(*heads.shape[1:])
        return grouped_row_gemm(
            h, gidx, heads, bn=bn, bk=bk,
            interpret=pallas_interpret(backend),
        )
    return _with_arange_fast_case(
        gidx, heads.shape[0],
        lambda h_, g_: ref.lm_head_rows_batched_ref(h_, heads),
        lambda h_, g_: ref.lm_head_rows_grouped_ref(h_, g_, heads),
        h, gidx,
    )
