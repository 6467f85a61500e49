"""Pure-jnp oracles for every Pallas kernel in this package."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def block_diag_matmul_ref(x: jax.Array, core: jax.Array, kappa: int) -> jax.Array:
    """y = x @ blockdiag(core x kappa);  x: (R, kappa*q), core: (q, q)."""
    R, F = x.shape
    q = core.shape[0]
    blocks = x.reshape(R, kappa, q)
    out = jnp.einsum(
        "rkq,qp->rkp", blocks.astype(jnp.float32), core.astype(jnp.float32)
    )
    return out.reshape(R, F).astype(x.dtype)


def block_diag_matmul_batched_ref(
    x: jax.Array, cores: jax.Array, kappa: int
) -> jax.Array:
    """Per-group morphing: each leading-axis group has its own core.

    x: (G, B, kappa*q), cores: (G, q, q)  ->  (G, B, kappa*q).
    """
    G, B, F = x.shape
    q = cores.shape[-1]
    blocks = x.reshape(G, B, kappa, q)
    out = jnp.einsum(
        "gbkq,gqp->gbkp", blocks.astype(jnp.float32), cores.astype(jnp.float32)
    )
    return out.reshape(G, B, F).astype(x.dtype)


def aug_gemm_batched_ref(t: jax.Array, c_acs: jax.Array) -> jax.Array:
    """Per-group Aug-Conv forward: t (G, B, K) @ c_acs (G, K, N) -> (G, B, N)."""
    return jnp.einsum(
        "gbk,gkn->gbn", t.astype(jnp.float32), c_acs.astype(jnp.float32)
    ).astype(t.dtype)


# -- slot-indexed grouped variants ------------------------------------------
#
# The grouped refs take the (G,) slot-index vector and the *stacked* (S, ...)
# secrets and never materialize the (G, ...) per-group copy: a lax.scan over
# the group axis dynamic-slices exactly one slot's secret per step, so peak
# extra memory is one secret tile (not G of them) and XLA runs each step as a
# plain GEMM.  They are both the correctness oracle for the Pallas kernels in
# ``grouped.py`` and the jnp backend's implementation, for every index vector.

def block_diag_matmul_grouped_ref(
    x: jax.Array, gidx: jax.Array, cores: jax.Array, kappa: int
) -> jax.Array:
    """Slot-indexed morphing: x (G, B, kappa*q), gidx (G,), cores (S, q, q)."""
    G, B, F = x.shape
    q = cores.shape[-1]

    def step(_, inp):
        xg, i = inp
        core = jax.lax.dynamic_index_in_dim(cores, i, 0, keepdims=False)
        blocks = xg.reshape(B, kappa, q)
        out = jnp.einsum(
            "bkq,qp->bkp", blocks.astype(jnp.float32), core.astype(jnp.float32)
        )
        return None, out.reshape(B, F).astype(x.dtype)

    _, out = jax.lax.scan(step, None, (x, gidx))
    return out


def aug_gemm_grouped_ref(
    t: jax.Array, gidx: jax.Array, c_acs: jax.Array
) -> jax.Array:
    """Slot-indexed Aug-Conv forward: t (G, B, K), gidx (G,), c_acs (S, K, N)."""

    def step(_, inp):
        tg, i = inp
        c = jax.lax.dynamic_index_in_dim(c_acs, i, 0, keepdims=False)
        out = jnp.dot(tg.astype(jnp.float32), c.astype(jnp.float32))
        return None, out.astype(t.dtype)

    _, out = jax.lax.scan(step, None, (t, gidx))
    return out


def aug_gemm_ref(t: jax.Array, c_ac: jax.Array) -> jax.Array:
    return jnp.dot(
        t.astype(jnp.float32), c_ac.astype(jnp.float32)
    ).astype(t.dtype)


def aug_embed_rows_grouped_ref(
    tokens: jax.Array, gidx: jax.Array, tables: jax.Array
) -> jax.Array:
    """Per-row slot-indexed AugE gather (batched decode: one token per row).

    tokens: (R,) int, gidx: (R,), tables: (S, V, d) -> (R, d): one gather
    of R rows, read in place from the stack.
    """
    return tables[gidx, tokens]


def lm_head_rows_grouped_ref(
    h: jax.Array, gidx: jax.Array, heads: jax.Array
) -> jax.Array:
    """Per-row slot-indexed LM-head GEMM: h (R, d), gidx (R,),
    heads (S, d, V) -> (R, V) logits.

    Contracts in ``h.dtype`` (weights cast to it), matching
    ``models.stack.lm_head`` — batched decode must emit bit-identical
    logits to the per-tenant loop.
    """

    def step(_, inp):
        hr, i = inp
        w = jax.lax.dynamic_index_in_dim(heads, i, 0, keepdims=False)
        return None, jnp.dot(hr, w.astype(hr.dtype))

    _, out = jax.lax.scan(step, None, (h, gidx))
    return out


def wkv6_ref(
    r: jax.Array, k: jax.Array, v: jax.Array, logw: jax.Array,
    u: jax.Array, s0: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Naive token-by-token RWKV-6 recurrence (the semantic oracle).

    r/k/v/logw: (B, H, T, D); u: (H, D); s0: (B, H, D, D).
      out_t = r_t (S_{t-1} + diag(u) k_t v_t^T);  S_t = diag(w_t) S_{t-1} + k_t v_t^T
    """
    def step(s, inp):
        rt, kt, vt, lwt = inp
        kv = jnp.einsum("bhd,bhv->bhdv", kt, vt)
        out = jnp.einsum("bhd,bhdv->bhv", rt, s + u[None, :, :, None] * kv)
        s_new = jnp.exp(lwt)[..., None] * s + kv
        return s_new, out

    xs = tuple(jnp.moveaxis(a, 2, 0) for a in (r, k, v, logw))
    s_fin, outs = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(outs, 0, 2), s_fin
