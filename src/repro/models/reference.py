"""Plain float32 forward of DeepSeek-V2's architecture (MLA + MoE), for tests.

The semantic oracle the served decode path is held to: one sequence, the
whole prompt at once, every product in float32 under
``jax.default_matmul_precision("highest")``, written from the published
architecture rather than from ``models.blocks``:

* MLA with no query compression: queries ``x W_q`` split into a 128-wide
  non-rotary and a 64-wide rotary part; keys and values decompressed from
  the RMS-normed latent ``c_kv = x W_dkv``; one rotary key ``x W_kr``
  shared by the heads; causal softmax attention.
* YaRN rope (DeepSeek's ``DeepseekV2YarnRotaryEmbedding``) and the softmax
  scale ``qk_head_dim ** -0.5 * mscale(factor, mscale_all_dim) ** 2``.
* Layer 0 a dense SwiGLU FFN; the others softmax-routed greedy top-k MoE,
  the k weights not renormalized, every expert computed on every token and
  masked by the routing, plus the shared experts.

No cache, no batching, no kernels.  Departures from the published model,
each a relabelling that changes no result the tests compare:

* RMSNorm scales by ``1 + w`` (the repo's parameterization: zero-initialized
  norms are the identity), where the checkpoint stores ``w``;
* rope rotates the halves ``(i, i + 32)`` of the rotary part, where the
  checkpoint's weights rotate interleaved pairs ``(2i, 2i + 1)``: a fixed
  permutation of ``W_kr``'s and ``W_q``'s rotary columns;
* the weights are the seeded initialization the server uses, not the
  checkpoint's, and the embedding and head come from the tenant's session.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["forward", "layers", "moe_dense", "yarn_inv_freq"]


def _rms(x, w, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1 + w)


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, base: float, y) -> np.ndarray:
    """YaRN inverse frequencies, float64 (``y`` None: plain rope)."""
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    if y is None:
        return extra
    inter = 1.0 / (y.factor * base ** (np.arange(0, dim, 2) / dim))

    def corr(rot):
        return dim * math.log(y.original_max_position / (rot * 2 * math.pi)) / (
            2 * math.log(base))

    lo = max(math.floor(corr(y.beta_fast)), 0)
    hi = min(math.ceil(corr(y.beta_slow)), dim - 1)
    if lo == hi:
        hi += 0.001
    mask = 1.0 - np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0, 1)
    return inter * (1 - mask) + extra * mask


def _rope(x, cfg):
    """x (L, ..., r) at positions 0..L-1, half-split rotation."""
    r = x.shape[-1]
    y = cfg.rope_scaling
    inv = yarn_inv_freq(r, cfg.rope_theta, y)
    ang = np.arange(x.shape[0])[:, None] * inv[None]          # (L, r/2)
    m = 1.0 if y is None else _mscale(y.factor, y.mscale) / _mscale(
        y.factor, y.mscale_all_dim)
    cos = jnp.asarray(np.cos(ang) * m, jnp.float32)
    sin = jnp.asarray(np.sin(ang) * m, jnp.float32)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (r // 2,)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    a, b = x[..., : r // 2], x[..., r // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _mla(p, x, cfg):
    a = cfg.mla
    L = x.shape[0]
    q = jnp.einsum("ld,dhk->lhk", x, p["wq"])
    q_nope, q_pe = q[..., : a.qk_nope], _rope(q[..., a.qk_nope:], cfg)
    ckv = _rms(x @ p["w_dkv"], p["kv_norm"])
    k_pe = _rope(x @ p["w_kr"], cfg)                            # (L, r)
    k_nope = jnp.einsum("lc,chk->lhk", ckv, p["w_uk"])
    v = jnp.einsum("lc,chv->lhv", ckv, p["w_uv"])
    scale = (a.qk_nope + a.qk_rope) ** -0.5
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= _mscale(y.factor, y.mscale_all_dim) ** 2
    s = (jnp.einsum("qhk,thk->hqt", q_nope, k_nope)
         + jnp.einsum("qhr,tr->hqt", q_pe, k_pe)) * scale
    s = jnp.where(np.tril(np.ones((L, L), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqt,thv->qhv", jax.nn.softmax(s, -1), v)
    return jnp.einsum("qhv,hvd->qd", o, p["wo"])


def _swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def moe_dense(p, x, cfg):
    """Every routed expert on every token, masked by the greedy top-k
    softmax routing (weights not renormalized unless ``norm_topk``), plus
    the shared experts."""
    m = cfg.moe
    probs = jax.nn.softmax(x @ p["router"], -1)                  # (L, E)
    top_p, top_i = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    gate = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], top_i].set(top_p)      # (L, E)
    h = jax.nn.silu(jnp.einsum("ld,edf->elf", x, p["wg"])) * jnp.einsum(
        "ld,edf->elf", x, p["wu"])
    y = jnp.einsum("elf,efd,le->ld", h, p["wd"], gate)
    if m.n_shared:
        s = p["shared"]
        y = y + _swiglu(x, s["wi_gate"], s["wi_up"], s["wo"])
    return y


def layers(params, cfg):
    """``(kind, layer params)`` in order, the scanned groups unstacked (or
    the serving steps' one-dict-a-layer form, ``{"layers": [...]}``)."""
    if "layers" in params:
        return list(zip(cfg.layer_kinds(), params["layers"]))
    out = [(k, params["prefix"][i]) for i, k in enumerate(cfg.prefix_pattern)]
    for g in range(cfg.n_groups):
        for i, k in enumerate(cfg.block_pattern):
            out.append((k, jax.tree.map(lambda a: a[g], params["blocks"][f"b{i}"])))
    out += [(k, params["suffix"][i]) for i, k in enumerate(cfg.suffix_pattern)]
    return out


def forward(params, embed, head, tokens, cfg) -> jax.Array:
    """Logits (L, V) of the plain (unmorphed) model over ``tokens`` (L,)."""
    f32 = lambda t: jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), t)
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(embed, jnp.float32)[jnp.asarray(tokens)]
        for kind, p in layers(params, cfg):
            p = f32(p)
            x = x + _mla(p["mix"], _rms(x, p["norm1"]), cfg)
            n = _rms(x, p["norm2"])
            x = x + (moe_dense(p["ffn"], n, cfg) if kind.endswith("_moe")
                     else _swiglu(n, p["ffn"]["wi_gate"], p["ffn"]["wi_up"],
                                  p["ffn"]["wo"]))
        x = _rms(x, jnp.asarray(params["final_norm"], jnp.float32))
        return x @ jnp.asarray(head, jnp.float32)
