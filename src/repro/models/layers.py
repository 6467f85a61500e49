"""Shared neural layers: norms, RoPE, attention (dense / flash-scan / decode),
gated MLPs.  Pure functions over explicit parameter dicts.

Attention supports the union of features needed by the assigned pool:
GQA (grouped KV heads), causal + sliding-window masks, attention-logit
soft-capping (gemma-2), bidirectional (whisper encoder) and cross attention,
and a memory-bounded *flash-scan* path (two-level Q/KV chunking with running
log-sum-exp) for long sequences — the pure-JAX analogue of FlashAttention,
structured so XLA keeps the working set at ``q_block x kv_block``.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Norms / activations
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    return (normed * (1.0 + weight.astype(jnp.float32))).astype(dt)


def layer_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    """Centered LN.  Like rms_norm, the scale is parameterized as (1 + w) so
    zero-initialized norm params mean identity scaling."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * (1.0 + weight.astype(jnp.float32))).astype(dt)


def norm(x: jax.Array, weight: jax.Array, kind: str) -> jax.Array:
    return rms_norm(x, weight) if kind == "rmsnorm" else layer_norm(x, weight)


def softcap(x: jax.Array, cap: float | None) -> jax.Array:
    if cap is None:
        return x
    return jnp.tanh(x / cap) * cap


def act_fn(kind: str):
    if kind in ("swiglu",):
        return jax.nn.silu
    if kind in ("geglu", "gelu"):
        return partial(jax.nn.gelu, approximate=True)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor ``0.1 * mscale * ln(factor) + 1``
    (1 when the context is not stretched)."""
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def rope_inv_freq(dim: int, theta: float, scaling=None) -> np.ndarray:
    """Inverse rotary frequencies of a ``dim``-wide rope, ``(dim // 2,)``.

    With ``scaling`` (a :class:`~repro.models.base.YarnCfg`), DeepSeek's
    YaRN: each frequency blends the interpolated ``f / factor`` and the
    extrapolated ``f`` by a linear ramp over the dimensions whose rotation
    count within ``original_max_position`` lies between ``beta_fast`` and
    ``beta_slow``.
    """
    half = dim // 2
    extra = theta ** (-np.arange(0, half, dtype=np.float64) / half)
    if scaling is None:
        return extra.astype(np.float32)

    def corr_dim(rotations: float) -> float:
        return (dim * math.log(scaling.original_max_position
                               / (rotations * 2 * math.pi))
                ) / (2 * math.log(theta))

    low = max(math.floor(corr_dim(scaling.beta_fast)), 0)
    high = min(math.ceil(corr_dim(scaling.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low),
                   0.0, 1.0)
    keep_extra = 1.0 - ramp
    inv = extra / scaling.factor * (1.0 - keep_extra) + extra * keep_extra
    return inv.astype(np.float32)


def rope(x: jax.Array, positions: jax.Array, theta: float,
         scaling=None) -> jax.Array:
    """Rotary embedding, half-split convention.

    x: (..., S, H, hd); positions: broadcastable to (..., S).  ``scaling``
    selects YaRN frequencies (:func:`rope_inv_freq`); its cos/sin factor
    ``mscale(mscale) / mscale(mscale_all_dim)`` is applied too.
    """
    hd = x.shape[-1]
    half = hd // 2
    if scaling is None:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        freqs = jnp.asarray(rope_inv_freq(hd, theta, scaling))
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]  # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    if scaling is not None:
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def _mask_bias(
    q_pos: jax.Array,          # (Sq,)
    kv_pos: jax.Array,         # (Skv,)
    causal: bool,
    window: int | None,
    kv_len: jax.Array | None,  # dynamic valid length (decode), scalar or (B,)
) -> jax.Array:
    """Additive mask (Sq, Skv) or (B, Sq, Skv); 0 = keep, -inf = drop."""
    ok = jnp.ones((q_pos.shape[0], kv_pos.shape[0]), dtype=bool)
    if causal:
        ok &= kv_pos[None, :] <= q_pos[:, None]
    if window is not None:
        ok &= kv_pos[None, :] > (q_pos[:, None] - window)
    if kv_len is not None:
        valid = kv_pos[None, :] < jnp.reshape(kv_len, (-1, 1))  # (B?, Skv)
        ok = ok[None] & valid[:, None, :]
    return jnp.where(ok, 0.0, -jnp.inf).astype(jnp.float32)


def dense_attention(
    q: jax.Array,              # (B, Sq, Hq, hd)
    k: jax.Array,              # (B, Skv, Hkv, hd)
    v: jax.Array,              # (B, Skv, Hkv, hd)
    *,
    causal: bool = True,
    window: int | None = None,
    logit_cap: float | None = None,
    q_pos: jax.Array | None = None,
    kv_pos: jax.Array | None = None,
    kv_len: jax.Array | None = None,
    scale: float | None = None,
) -> jax.Array:
    """Unfused attention: full (Sq, Skv) score matrix, fp32 softmax."""
    B, Sq, Hq, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = (hd ** -0.5) if scale is None else scale
    if q_pos is None:
        q_pos = jnp.arange(Sq)
    if kv_pos is None:
        kv_pos = jnp.arange(Skv)

    qg = q.reshape(B, Sq, Hkv, G, hd)
    logits = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32
    ) * scale
    logits = softcap(logits, logit_cap)
    bias = _mask_bias(q_pos, kv_pos, causal, window, kv_len)
    if bias.ndim == 3:  # (B, Sq, Skv)
        bias = bias[:, None, None]
    logits = logits + bias
    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w, v)
    return out.reshape(B, Sq, Hq, hd)


def flash_attention(
    q: jax.Array,              # (B, Sq, Hq, hd)
    k: jax.Array,              # (B, Skv, Hkv, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    logit_cap: float | None = None,
    q_offset: int = 0,         # absolute position of q[0] (chunked prefill)
    block_q: int = 512,
    block_kv: int = 1024,
    scale: float | None = None,
) -> jax.Array:
    """Two-level chunked attention with running log-sum-exp.

    Peak working set is O(block_q x block_kv) per head instead of Sq x Skv.
    Causal block-skipping: KV blocks strictly in the future of a whole Q block
    contribute exactly zero; we still *compute* them under mask (static-shape
    scan) but their cost is measured and attacked in the §Perf pass via the
    triangular schedule (see sharding/perf notes).
    """
    B, Sq, Hq, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = (hd ** -0.5) if scale is None else scale
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    assert Sq % block_q == 0 and Skv % block_kv == 0, (Sq, block_q, Skv, block_kv)
    nq, nk = Sq // block_q, Skv // block_kv

    qg = q.reshape(B, nq, block_q, Hkv, G, hd)
    kb = k.reshape(B, nk, block_kv, Hkv, hd)
    vb = v.reshape(B, nk, block_kv, Hkv, hd)

    def q_block(qi, qblk):
        # qblk: (B, block_q, Hkv, G, hd)
        q_pos = q_offset + qi * block_q + jnp.arange(block_q)

        def kv_step(carry, inp):
            acc, m, l = carry
            ki, kblk, vblk = inp
            kv_pos = ki * block_kv + jnp.arange(block_kv)
            s = jnp.einsum(
                "bqhgd,bkhd->bhgqk", qblk, kblk,
                preferred_element_type=jnp.float32,
            ) * scale
            s = softcap(s, logit_cap)
            ok = jnp.ones((block_q, block_kv), dtype=bool)
            if causal:
                ok &= kv_pos[None, :] <= q_pos[:, None]
            if window is not None:
                ok &= kv_pos[None, :] > (q_pos[:, None] - window)
            s = jnp.where(ok[None, None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            # guard: fully-masked rows keep m=-inf; exp(-inf - -inf) -> use safe m
            m_safe = jnp.where(jnp.isinf(m_new), 0.0, m_new)
            p = jnp.exp(s - m_safe[..., None])
            corr = jnp.exp(jnp.where(jnp.isinf(m), -jnp.inf, m - m_safe))
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32,
            )
            acc_new = acc * corr[..., None] + pv
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((B, Hkv, G, block_q, hd), jnp.float32)
        m0 = jnp.full((B, Hkv, G, block_q), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, block_q), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(
            kv_step, (acc0, m0, l0), (jnp.arange(nk), kb.swapaxes(0, 1), vb.swapaxes(0, 1))
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out  # (B, Hkv, G, block_q, hd)

    outs = jax.lax.map(lambda args: q_block(*args), (jnp.arange(nq), qg.swapaxes(0, 1)))
    # outs: (nq, B, Hkv, G, block_q, hd) -> (B, Sq, Hq, hd)
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sq, Hq, hd)
    return out.astype(q.dtype)


def decode_attention(
    q: jax.Array,              # (B, 1, Hq, hd)
    k_cache: jax.Array,        # (B, Smax, Hkv, hd)
    v_cache: jax.Array,
    t: jax.Array,              # current length (new token written at t); scalar
    *,
    window: int | None = None,
    logit_cap: float | None = None,
    scale: float | None = None,
) -> jax.Array:
    """Single-token attention against a (possibly sequence-sharded) KV cache."""
    B, _, Hq, hd = q.shape
    Smax, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    scale = (hd ** -0.5) if scale is None else scale
    qg = q.reshape(B, Hkv, G, hd)
    s = jnp.einsum(
        "bhgd,bkhd->bhgk", qg, k_cache, preferred_element_type=jnp.float32
    ) * scale
    s = softcap(s, logit_cap)
    kv_pos = jnp.arange(Smax)
    ok = kv_pos[None] <= t  # positions 0..t valid
    if window is not None:
        ok &= kv_pos[None] > (t - window)
    s = jnp.where(ok[:, None, None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bhgk,bkhd->bhgd", w, v_cache)
    return out.reshape(B, 1, Hq, hd)


def attention(
    q, k, v, *, causal=True, window=None, logit_cap=None, q_offset=0,
    dense_max_seq=1024, block_kv=1024, scale=None,
):
    """Dispatch dense vs flash-scan by sequence length."""
    if q.shape[1] * k.shape[1] <= dense_max_seq * dense_max_seq:
        return dense_attention(
            q, k, v, causal=causal, window=window, logit_cap=logit_cap,
            q_pos=q_offset + jnp.arange(q.shape[1]), kv_pos=jnp.arange(k.shape[1]),
            scale=scale,
        )
    return flash_attention(
        q, k, v, causal=causal, window=window, logit_cap=logit_cap,
        q_offset=q_offset, block_kv=block_kv, scale=scale,
    )


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def gated_mlp(x: jax.Array, wi_gate: jax.Array, wi_up: jax.Array,
              wo: jax.Array, act: str) -> jax.Array:
    """SwiGLU / GeGLU: act(x @ wi_gate) * (x @ wi_up) @ wo."""
    g = act_fn(act)(jnp.einsum("...d,df->...f", x, wi_gate))
    u = jnp.einsum("...d,df->...f", x, wi_up)
    return jnp.einsum("...f,fd->...d", g * u, wo)
