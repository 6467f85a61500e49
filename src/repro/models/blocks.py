"""Per-layer blocks: schema + apply for every layer kind in the assigned pool.

Layer kinds (``ModelConfig.block_pattern`` entries):
  "attn"      global self-attention + dense FFN
  "local"     sliding-window self-attention + dense FFN
  "mla"       DeepSeek-V2 multi-head latent attention + dense FFN
  "attn_moe" / "local_moe" / "mla_moe"   — same mixers with MoE FFN
  "rec"       RG-LRU recurrent block (Griffin) + dense FFN
  "rwkv"      RWKV-6 time-mix + channel-mix (attention-free)
  "cross"     gated cross-attention layer (llama-3.2-vision style)
  "bidir"     bidirectional self-attention + FFN (whisper encoder)
  "dec"       self-attn + cross-attn + FFN (whisper decoder)

Every kind provides:
  schema_<kind>(cfg)                          -> ParamDef tree
  apply_<kind>(p, h, cfg, rs, cache)          -> (h, cache')
  cache_<kind>(cfg, batch, max_len)           -> ParamDef tree for its cache

``rs`` is a RunState: mode ("full" for train/prefill, "decode"), decode
position ``t`` (a scalar; MLA also takes one per row), optional cross-attention
context.  In "full" mode with a cache
tree supplied, blocks also *write* their caches (prefill).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .base import ModelConfig, ParamDef
from . import layers as L

# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RunState:
    mode: str                       # "full" | "decode"
    t: jax.Array | None = None      # decode: position being written (scalar)
    ctx: jax.Array | None = None    # cross-attn context embeds (B, Sc, d_ctx)
    write_cache: bool = False       # prefill: emit caches in full mode


def mixer_of(kind: str) -> str:
    return kind[: -len("_moe")] if kind.endswith("_moe") else kind


def ffn_of(kind: str) -> str:
    if kind.endswith("_moe"):
        return "moe"
    if kind == "rwkv":
        return "rwkv_cm"
    return "dense"


# ---------------------------------------------------------------------------
# Dense FFN / MoE FFN
# ---------------------------------------------------------------------------


def schema_ffn(cfg: ModelConfig, d_ff: int | None = None) -> dict:
    d, f = cfg.d_model, (d_ff or cfg.d_ff)
    if cfg.act == "gelu":  # plain (ungated) MLP, whisper-style
        return {
            "wi_up": ParamDef((d, f), ("embed", "ffn")),
            "wo": ParamDef((f, d), ("ffn", "embed")),
        }
    return {
        "wi_gate": ParamDef((d, f), ("embed", "ffn")),
        "wi_up": ParamDef((d, f), ("embed", "ffn")),
        "wo": ParamDef((f, d), ("ffn", "embed")),
    }


def apply_ffn(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.act == "gelu":
        h = L.act_fn("gelu")(jnp.einsum("...d,df->...f", x, p["wi_up"]))
        return jnp.einsum("...f,fd->...d", h, p["wo"])
    return L.gated_mlp(x, p["wi_gate"], p["wi_up"], p["wo"], cfg.act)


def schema_moe(cfg: ModelConfig) -> dict:
    m = cfg.moe
    d, f, e = cfg.d_model, m.d_ff_expert, m.n_routed
    sch = {
        "router": ParamDef((d, e), ("embed", None), scale=0.02),
        "wg": ParamDef((e, d, f), ("experts", "embed", "ffn")),
        "wu": ParamDef((e, d, f), ("experts", "embed", "ffn")),
        "wd": ParamDef((e, f, d), ("experts", "ffn", "embed")),
    }
    if m.n_shared:
        sch["shared"] = schema_ffn(cfg, d_ff=m.n_shared * f)
    return sch


def apply_moe(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """MoE FFN dispatcher.

    Under a mesh with a "model" axis, uses the shard_map EP implementation
    (each device dispatches only its LOCAL tokens to its LOCAL experts and the
    partial outputs are psum'd over "model" — full data-parallelism preserved;
    see EXPERIMENTS.md §Perf hillclimb 1).  Without a mesh (serving, smoke
    tests, single-device runs), the dropless form :func:`moe_dropless`.
    """
    mesh = _ambient_mesh()
    if mesh is not None and "model" in mesh.axis_names:
        return _apply_moe_sharded(p, x, cfg, mesh)
    B, S, d = x.shape
    y, _ = moe_dropless(p, x.reshape(B * S, d), cfg)
    return y.reshape(B, S, d)


def _ambient_mesh():
    try:
        m = jax.sharding.get_abstract_mesh()
    except Exception:  # pragma: no cover
        return None
    if m is None or not m.axis_names:
        return None
    return m


def moe_route(p: dict, xf: jax.Array, cfg: ModelConfig):
    """Softmax router, greedy top-k: ``(weights (T, k) f32, experts (T, k))``;
    the weights are renormalized over the k only when ``norm_topk``."""
    m = cfg.moe
    logits = jnp.einsum(
        "td,de->te", xf.astype(jnp.float32), p["router"].astype(jnp.float32)
    )
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return top_p, top_i


def moe_dropless(p: dict, xf: jax.Array, cfg: ModelConfig,
                 backend: str | None = None,
                 keep: jax.Array | None = None) -> tuple[jax.Array, jax.Array]:
    """Dropless MoE FFN over tokens ``xf (T, d)``: every (token, expert) pair
    is served, whatever the routing.  Tokens outside ``keep`` (T,) bool, if
    given (a decode step's idle rows, a prefill's padding), route nowhere.

    The pairs are sorted by expert and run as one grouped GEMM per weight
    (``kernels.ops.expert_ffn``): only the experts some token chose are
    read.  Returns ``(y (T, d), experts hit)``, the second an int32 scalar.
    """
    from ..kernels.ops import expert_ffn

    m = cfg.moe
    top_p, top_i = moe_route(p, xf, cfg)
    y = expert_ffn(xf, top_i, top_p, p["wg"], p["wu"], p["wd"],
                   act=cfg.act, keep=keep, backend=backend)
    if keep is not None:
        top_i = jnp.where(keep[:, None], top_i, m.n_routed)
    hit = jnp.zeros((m.n_routed,), bool).at[top_i.reshape(-1)].set(
        True, mode="drop")
    if m.n_shared:
        y = y + apply_ffn(p["shared"], xf, cfg)
    return y, jnp.sum(hit, dtype=jnp.int32)


def _moe_local_tokens(p_local: dict, xf: jax.Array, cfg: ModelConfig,
                      e_lo: jax.Array, n_local: int) -> jax.Array:
    """Per-device EP dispatch: route LOCAL tokens to the n_local LOCAL experts
    [e_lo, e_lo + n_local); returns this shard's PARTIAL output (T_loc, d)."""
    m = cfg.moe
    T, d = xf.shape
    logits = jnp.einsum(
        "td,de->te", xf.astype(jnp.float32), p_local["router"].astype(jnp.float32)
    )
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, m.top_k)          # (T, k) global ids
    if m.norm_topk:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    local = top_i - e_lo                                   # local expert ids
    is_local = (local >= 0) & (local < n_local)
    C = max(8, -(-int(np.ceil(T * m.top_k * m.capacity_factor / m.n_routed)) // 8) * 8)

    e_flat = jnp.where(is_local, local, n_local).reshape(-1)   # n_local = drop bin
    onehot = jax.nn.one_hot(e_flat, n_local + 1, dtype=jnp.int32)
    pos = jnp.take_along_axis(
        jnp.cumsum(onehot, axis=0) - 1, e_flat[:, None], axis=1
    )[:, 0]
    keep = (e_flat < n_local) & (pos < C)
    e_c = jnp.where(keep, e_flat, 0)
    pos_c = jnp.where(keep, pos, C - 1)

    tok_idx = jnp.repeat(jnp.arange(T), m.top_k)
    gathered = xf[tok_idx] * keep[:, None].astype(xf.dtype)
    buf = jnp.zeros((n_local, C, d), xf.dtype).at[e_c, pos_c].add(gathered)

    g = L.act_fn(cfg.act)(jnp.einsum("ecd,edf->ecf", buf, p_local["wg"]))
    u = jnp.einsum("ecd,edf->ecf", buf, p_local["wu"])
    out_buf = jnp.einsum("ecf,efd->ecd", g * u, p_local["wd"])

    picked = out_buf[e_c, pos_c] * keep[:, None].astype(xf.dtype)
    w = top_p.reshape(-1).astype(xf.dtype)
    return jnp.sum((picked * w[:, None]).reshape(T, m.top_k, d), axis=1)


def _apply_moe_sharded(p: dict, x: jax.Array, cfg: ModelConfig, mesh) -> jax.Array:
    """shard_map EP: tokens stay sharded over the dp axes, experts over
    "model"; each device runs the dispatch for its (T_loc x E_loc) block and
    partial outputs (each token's top-k experts live on != model shards) are
    combined with one psum over "model" — the same collective shape as a
    row-parallel matmul, replacing the replicated global dispatch."""
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    names = set(mesh.axis_names)
    dp = tuple(a for a in ("pod", "data") if a in names)
    B, S, d = x.shape
    dp_size = int(np.prod([dict(zip(mesh.axis_names, mesh.axis_sizes))[a] for a in dp])) if dp else 1
    model_size = dict(zip(mesh.axis_names, mesh.axis_sizes))["model"]
    if (B % max(dp_size, 1) != 0) or (m.n_routed % model_size != 0):
        y, _ = moe_dropless(p, x.reshape(B * S, d), cfg)
        return y.reshape(B, S, d)
    n_local = m.n_routed // model_size

    x_spec = P(dp if dp else None, None, None)
    w_spec = {
        "router": P(None, None),
        "wg": P("model", None, None),
        "wu": P("model", None, None),
        "wd": P("model", None, None),
    }
    if m.n_shared:
        # shared experts run row-parallel over "model" (partial sums join the
        # same psum as the routed outputs)
        w_spec["shared"] = {
            "wi_gate": P(None, "model"), "wi_up": P(None, "model"),
            "wo": P("model", None),
        }

    def inner(x_loc, p_loc):
        Bl, Sl, _ = x_loc.shape
        xf = x_loc.reshape(Bl * Sl, d)
        e_lo = jax.lax.axis_index("model") * n_local
        y = _moe_local_tokens(p_loc, xf, cfg, e_lo, n_local)
        if m.n_shared:
            sp = p_loc["shared"]
            g = L.act_fn(cfg.act)(xf @ sp["wi_gate"])
            y = y + (g * (xf @ sp["wi_up"])) @ sp["wo"]
        y = jax.lax.psum(y, "model")
        return y.reshape(Bl, Sl, d)

    return jax.shard_map(
        inner, mesh=mesh, in_specs=(x_spec, w_spec), out_specs=x_spec,
        check_vma=False,
    )(x, p)


# ---------------------------------------------------------------------------
# Self-attention mixer (global / local / bidir)
# ---------------------------------------------------------------------------


def schema_attn(cfg: ModelConfig) -> dict:
    d, H, Hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    sch = {
        "wq": ParamDef((d, H, hd), ("embed", "heads", None)),
        "wk": ParamDef((d, Hkv, hd), ("embed", "kv_heads", None)),
        "wv": ParamDef((d, Hkv, hd), ("embed", "kv_heads", None)),
        "wo": ParamDef((H, hd, d), ("heads", None, "embed"), scale=0.02),
    }
    if cfg.qkv_bias:
        sch["bq"] = ParamDef((H, hd), ("heads", None), init="zeros")
        sch["bk"] = ParamDef((Hkv, hd), ("kv_heads", None), init="zeros")
        sch["bv"] = ParamDef((Hkv, hd), ("kv_heads", None), init="zeros")
    return sch


def cache_attn(cfg: ModelConfig, batch: int, max_len: int, window: int | None) -> dict:
    Hkv, hd = cfg.n_kv_heads, cfg.head_dim
    slots = min(max_len, window) if window else max_len
    return {
        "k": ParamDef((batch, slots, Hkv, hd), ("batch", "kv_seq", "kv_heads", None), init="zeros"),
        "v": ParamDef((batch, slots, Hkv, hd), ("batch", "kv_seq", "kv_heads", None), init="zeros"),
        # absolute position held by each slot; -1 = empty (ring buffer for
        # windowed layers: slot(pos) = pos % slots)
        "pos": ParamDef((slots,), (None,), init="neg_ones", dtype="int32"),
    }


def _qkv(p: dict, h: jax.Array, cfg: ModelConfig):
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return q, k, v


def apply_attn(
    p: dict, h: jax.Array, cfg: ModelConfig, rs: RunState,
    cache: dict | None, *, window: int | None, causal: bool = True,
) -> tuple[jax.Array, dict | None]:
    B = h.shape[0]
    q, k, v = _qkv(p, h, cfg)

    if rs.mode == "decode":
        t = rs.t
        slots = cache["k"].shape[1]
        slot = t % slots if window else t
        q = L.rope(q, jnp.full((B, 1), t), cfg.rope_theta)
        k = L.rope(k, jnp.full((B, 1), t), cfg.rope_theta)
        kc = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
        pos = jax.lax.dynamic_update_slice_in_dim(
            cache["pos"], jnp.asarray([t], cache["pos"].dtype), slot, axis=0
        )
        # mask by recorded absolute positions (ring-buffer correct for windows)
        valid = (pos >= 0) & (pos <= t)
        if window:
            valid &= pos > (t - window)
        qg = q.reshape(B, cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.head_dim)
        s = jnp.einsum("bhgd,bkhd->bhgk", qg, kc, preferred_element_type=jnp.float32)
        scale = cfg.attn_scale if cfg.attn_scale is not None else cfg.head_dim ** -0.5
        s = L.softcap(s * scale, cfg.attn_softcap)
        s = jnp.where(valid[None, None, None, :], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1).astype(vc.dtype)
        o = jnp.einsum("bhgk,bkhd->bhgd", w, vc).reshape(B, 1, cfg.n_heads, cfg.head_dim)
        new_cache = {"k": kc, "v": vc, "pos": pos}
    else:
        S = h.shape[1]
        positions = jnp.arange(S)
        q = L.rope(q, positions[None], cfg.rope_theta)
        k = L.rope(k, positions[None], cfg.rope_theta)
        o = L.attention(
            q, k, v, causal=causal, window=window, logit_cap=cfg.attn_softcap,
            dense_max_seq=cfg.dense_attn_max_seq, block_kv=cfg.flash_block_kv,
            scale=cfg.attn_scale,
        )
        new_cache = None
        if cache is not None and rs.write_cache:
            slots = cache["k"].shape[1]
            keep = min(slots, S)
            # ring placement: position p lives at slot p % slots, so that
            # subsequent decode writes (slot = t % slots) stay consistent.
            ps = positions[-keep:]
            idx = ps % slots
            new_cache = {
                "k": cache["k"].at[:, idx].set(k[:, -keep:].astype(cache["k"].dtype)),
                "v": cache["v"].at[:, idx].set(v[:, -keep:].astype(cache["v"].dtype)),
                "pos": cache["pos"].at[idx].set(ps.astype(cache["pos"].dtype)),
            }

    out = jnp.einsum("bshk,hkd->bsd", o.astype(h.dtype), p["wo"])
    return out, new_cache


# ---------------------------------------------------------------------------
# Cross-attention mixer (vlm "cross", whisper "dec" second sublayer)
# ---------------------------------------------------------------------------


def schema_cross(cfg: ModelConfig, gated: bool, d_ctx: int | None = None) -> dict:
    d, H, Hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if d_ctx is None:
        d_ctx = cfg.frontend.d_in if cfg.frontend else d
    sch = {
        "wq": ParamDef((d, H, hd), ("embed", "heads", None)),
        "wk": ParamDef((d_ctx, Hkv, hd), (None, "kv_heads", None)),
        "wv": ParamDef((d_ctx, Hkv, hd), (None, "kv_heads", None)),
        "wo": ParamDef((H, hd, d), ("heads", None, "embed"), scale=0.02),
        "ctx_norm": ParamDef((d_ctx,), (None,), init="zeros"),
    }
    if gated:
        sch["gate_attn"] = ParamDef((), (), init="zeros")
        sch["gate_ffn"] = ParamDef((), (), init="zeros")
    return sch


def cache_cross(cfg: ModelConfig, batch: int) -> dict:
    Hkv, hd = cfg.n_kv_heads, cfg.head_dim
    n_ctx = cfg.frontend.n_tokens if cfg.frontend else 0
    return {
        "k": ParamDef((batch, n_ctx, Hkv, hd), ("batch", None, "kv_heads", None), init="zeros"),
        "v": ParamDef((batch, n_ctx, Hkv, hd), ("batch", None, "kv_heads", None), init="zeros"),
    }


def apply_cross(
    p: dict, h: jax.Array, cfg: ModelConfig, rs: RunState, cache: dict | None
) -> tuple[jax.Array, dict | None]:
    B = h.shape[0]
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
    if rs.mode == "decode":
        k, v = cache["k"], cache["v"]  # static context KV from prefill
        new_cache = cache
    else:
        ctx = L.rms_norm(rs.ctx, p["ctx_norm"])
        k = jnp.einsum("bsd,dhk->bshk", ctx, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", ctx, p["wv"])
        new_cache = None
        if cache is not None and rs.write_cache:
            new_cache = {"k": k.astype(cache["k"].dtype), "v": v.astype(cache["v"].dtype)}
    o = L.dense_attention(q, k, v, causal=False)
    out = jnp.einsum("bshk,hkd->bsd", o.astype(h.dtype), p["wo"])
    return out, new_cache


# ---------------------------------------------------------------------------
# MLA mixer (DeepSeek-V2)
# ---------------------------------------------------------------------------


def schema_mla(cfg: ModelConfig) -> dict:
    d, H = cfg.d_model, cfg.n_heads
    a = cfg.mla
    # The up-projections contract over d and kv_lora, not over the heads
    # axis init_params would take as fan-in: scaled so, queries and keys
    # come out near unit variance and the attention is not saturated.
    return {
        "wq": ParamDef((d, H, a.qk_nope + a.qk_rope), ("embed", "heads", None),
                       scale=d ** -0.5),
        "w_dkv": ParamDef((d, a.kv_lora), ("embed", "lora")),
        "w_kr": ParamDef((d, a.qk_rope), ("embed", None)),
        "kv_norm": ParamDef((a.kv_lora,), ("lora",), init="zeros"),
        "w_uk": ParamDef((a.kv_lora, H, a.qk_nope), ("lora", "heads", None),
                         scale=a.kv_lora ** -0.5),
        "w_uv": ParamDef((a.kv_lora, H, a.v_head), ("lora", "heads", None),
                         scale=a.kv_lora ** -0.5),
        "wo": ParamDef((H, a.v_head, d), ("heads", None, "embed"), scale=0.02),
    }


def cache_mla(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    a = cfg.mla
    return {
        "ckv": ParamDef((batch, max_len, a.kv_lora), ("batch", "kv_seq", "lora"), init="zeros"),
        "kr": ParamDef((batch, max_len, a.qk_rope), ("batch", "kv_seq", None), init="zeros"),
    }


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """MLA's attention scale: ``qk_head_dim ** -0.5``, times YaRN's
    ``mscale(factor, mscale_all_dim) ** 2`` when the rope is stretched."""
    a = cfg.mla
    scale = (a.qk_nope + a.qk_rope) ** -0.5
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        m = L.yarn_mscale(y.factor, y.mscale_all_dim)
        scale *= m * m
    return scale


def _mla_rope(x: jax.Array, positions: jax.Array, cfg: ModelConfig) -> jax.Array:
    return L.rope(x, positions, cfg.rope_theta, cfg.rope_scaling)


def mla_decode_rows(
    p: dict, h: jax.Array, cfg: ModelConfig, t: jax.Array,
    ckv: jax.Array, kr: jax.Array,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One new token per row against a read-only latent cache, in the
    *absorbed* form (``W_uk``/``W_uv`` folded into the score and output
    einsums).

    h (B, 1, d); t (B,) the position each row writes; ckv (B, T, lora) and
    kr (B, T, qk_rope) hold each row's positions ``< t`` (later slots are
    ignored).  The new token attends to the cache and to itself.  Returns
    ``(out (B, 1, d), ckv_new (B, lora), kr_new (B, qk_rope))``: the caller
    writes the new entries at ``t``.
    """
    a = cfg.mla
    pos = t[:, None]
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
    q_nope, q_rope = q[..., : a.qk_nope], q[..., a.qk_nope :]
    q_rope = _mla_rope(q_rope, pos, cfg)[:, 0]                       # (B,H,r)
    ckv_new = L.rms_norm(jnp.einsum("bsd,dl->bsl", h, p["w_dkv"]),
                         p["kv_norm"])[:, 0]                            # (B,l)
    kr_new = _mla_rope(jnp.einsum("bsd,dr->bsr", h, p["w_kr"])[:, :, None],
                       pos, cfg)[:, 0, 0]                               # (B,r)
    ckv_new, kr_new = ckv_new.astype(ckv.dtype), kr_new.astype(kr.dtype)
    q_eff = jnp.einsum("bhk,lhk->bhl", q_nope[:, 0], p["w_uk"]).astype(ckv.dtype)
    q_rope = q_rope.astype(kr.dtype)
    f32 = jnp.float32
    s = (jnp.einsum("bhl,btl->bht", q_eff, ckv, preferred_element_type=f32)
         + jnp.einsum("bhr,btr->bht", q_rope, kr, preferred_element_type=f32))
    s_self = (jnp.einsum("bhl,bl->bh", q_eff, ckv_new, preferred_element_type=f32)
              + jnp.einsum("bhr,br->bh", q_rope, kr_new,
                           preferred_element_type=f32))
    valid = jnp.arange(ckv.shape[1])[None] < t[:, None]                 # (B,T)
    s = jnp.where(valid[:, None], s, -jnp.inf)
    w = jax.nn.softmax(
        jnp.concatenate([s, s_self[..., None]], axis=-1) * mla_softmax_scale(cfg),
        axis=-1,
    )
    ctx = (jnp.einsum("bht,btl->bhl", w[..., :-1].astype(ckv.dtype), ckv,
                      preferred_element_type=f32)
           + w[..., -1:] * ckv_new.astype(f32)[:, None])                # (B,H,l)
    o = jnp.einsum("bhl,lhv->bhv", ctx.astype(h.dtype), p["w_uv"])
    out = jnp.einsum("bshv,hvd->bsd", o[:, None].astype(h.dtype), p["wo"])
    return out, ckv_new, kr_new


def apply_mla(
    p: dict, h: jax.Array, cfg: ModelConfig, rs: RunState, cache: dict | None
) -> tuple[jax.Array, dict | None]:
    """MLA: full (decompressed) form for training/prefill; *absorbed* form for
    decode (:func:`mla_decode_rows`) — the cache stores only (c_kv, k_rope)
    per token (the paper's KV-cache compression).  In decode, ``rs.t`` is one
    position for the batch or one per row, shape (B,).
    """
    a = cfg.mla
    B = h.shape[0]
    H = cfg.n_heads

    if rs.mode == "decode":
        t = jnp.broadcast_to(jnp.asarray(rs.t, jnp.int32), (B,))
        out, ckv_new, kr_new = mla_decode_rows(
            p, h, cfg, t, cache["ckv"], cache["kr"]
        )
        rows = jnp.arange(B)
        new_cache = {"ckv": cache["ckv"].at[rows, t].set(ckv_new),
                     "kr": cache["kr"].at[rows, t].set(kr_new)}
        return out, new_cache

    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
    q_nope, q_rope = q[..., : a.qk_nope], q[..., a.qk_nope :]
    S = h.shape[1]
    positions = jnp.arange(S)[None]
    q_rope = _mla_rope(q_rope, positions, cfg)
    ckv = L.rms_norm(jnp.einsum("bsd,dl->bsl", h, p["w_dkv"]), p["kv_norm"])
    kr = _mla_rope(jnp.einsum("bsd,dr->bsr", h, p["w_kr"])[:, :, None],
                   positions, cfg)
    k_nope = jnp.einsum("bsl,lhk->bshk", ckv, p["w_uk"])
    v = jnp.einsum("bsl,lhv->bshv", ckv, p["w_uv"])
    qf = jnp.concatenate([q_nope, q_rope], axis=-1)
    kf = jnp.concatenate([k_nope, jnp.broadcast_to(kr, (B, S, H, a.qk_rope))], axis=-1)
    pad = a.qk_nope + a.qk_rope - a.v_head
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, pad))) if pad else v
    o = L.attention(
        qf, kf, vp, causal=True, logit_cap=None, scale=mla_softmax_scale(cfg),
        dense_max_seq=cfg.dense_attn_max_seq, block_kv=cfg.flash_block_kv,
    )[..., : a.v_head]
    new_cache = None
    if cache is not None and rs.write_cache:
        new_cache = {
            "ckv": jnp.zeros_like(cache["ckv"]).at[:, :S].set(ckv.astype(cache["ckv"].dtype)),
            "kr": jnp.zeros_like(cache["kr"]).at[:, :S].set(kr[:, :, 0].astype(cache["kr"].dtype)),
        }

    out = jnp.einsum("bshv,hvd->bsd", o, p["wo"])
    return out, new_cache


# ---------------------------------------------------------------------------
# RG-LRU recurrent mixer (Griffin / RecurrentGemma)
# ---------------------------------------------------------------------------


def schema_rec(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    r = cfg.rnn
    dr = r.d_rnn or d
    nb = 16  # block-diagonal gate blocks (RecurrentGemma-style)
    bw = dr // nb
    return {
        "w_y": ParamDef((d, dr), ("embed", "rnn")),
        "w_x": ParamDef((d, dr), ("embed", "rnn")),
        "conv_w": ParamDef((r.conv_width, dr), (None, "rnn"), scale=0.02),
        "conv_b": ParamDef((dr,), ("rnn",), init="zeros"),
        "gate_a": ParamDef((nb, bw, bw), ("rnn", None, None)),
        "gate_a_b": ParamDef((dr,), ("rnn",), init="zeros"),
        "gate_x": ParamDef((nb, bw, bw), ("rnn", None, None)),
        "gate_x_b": ParamDef((dr,), ("rnn",), init="zeros"),
        "lam": ParamDef((dr,), ("rnn",), init="normal", scale=0.5),
        "w_out": ParamDef((dr, d), ("rnn", "embed"), scale=0.02),
    }


def cache_rec(cfg: ModelConfig, batch: int) -> dict:
    r = cfg.rnn
    dr = r.d_rnn or cfg.d_model
    return {
        "h": ParamDef((batch, dr), ("batch", "rnn"), init="zeros", dtype="float32"),
        "conv": ParamDef((batch, r.conv_width - 1, dr), ("batch", None, "rnn"), init="zeros"),
    }


def _block_diag_gate(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """x: (..., dr) -> sigmoid(blockdiag(w) x + b); w: (nb, bw, bw)."""
    nb, bw, _ = w.shape
    lead = x.shape[:-1]
    xb = x.reshape(*lead, nb, bw)
    y = jnp.einsum("...nb,nbc->...nc", xb, w).reshape(*lead, nb * bw)
    return jax.nn.sigmoid((y + b).astype(jnp.float32))


def _rglru(z: jax.Array, p: dict, cfg: ModelConfig, h0: jax.Array | None):
    """RG-LRU over (B, S, dr) via associative scan; returns (out, h_last)."""
    c = cfg.rnn.c
    r_gate = _block_diag_gate(z, p["gate_a"], p["gate_a_b"])        # recurrence gate
    i_gate = _block_diag_gate(z, p["gate_x"], p["gate_x_b"])        # input gate
    log_a = -c * jax.nn.softplus(p["lam"].astype(jnp.float32)) * r_gate
    a = jnp.exp(log_a)
    gated_x = (z.astype(jnp.float32) * i_gate)
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * gated_x
    if h0 is not None:
        # fold initial state into the first step: b_0 += a_0 * h0
        b = b.at[:, 0].add(a[:, 0] * h0.astype(jnp.float32))

    def combine(x, y):
        a1, b1 = x
        a2, b2 = y
        return a2 * a1, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    return h, h[:, -1]


def apply_rec(
    p: dict, h: jax.Array, cfg: ModelConfig, rs: RunState, cache: dict | None
) -> tuple[jax.Array, dict | None]:
    r = cfg.rnn
    y = jax.nn.gelu(jnp.einsum("bsd,dr->bsr", h, p["w_y"]))
    z = jnp.einsum("bsd,dr->bsr", h, p["w_x"])

    if rs.mode == "decode":
        # temporal conv over (conv_state ++ z)
        zc = jnp.concatenate([cache["conv"], z], axis=1)  # (B, W, dr)
        z1 = jnp.einsum("bwr,wr->br", zc, p["conv_w"]) + p["conv_b"]
        rg = _block_diag_gate(z1, p["gate_a"], p["gate_a_b"])
        ig = _block_diag_gate(z1, p["gate_x"], p["gate_x_b"])
        log_a = -r.c * jax.nn.softplus(p["lam"].astype(jnp.float32)) * rg
        a = jnp.exp(log_a)
        b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * (
            z1.astype(jnp.float32) * ig
        )
        hn = a * cache["h"].astype(jnp.float32) + b
        out = (y[:, 0] * hn.astype(h.dtype)) @ p["w_out"]
        new_cache = {"h": hn.astype(cache["h"].dtype), "conv": zc[:, 1:]}
        return out[:, None], new_cache
    else:
        W = r.conv_width
        zp = jnp.pad(z, ((0, 0), (W - 1, 0), (0, 0)))
        zc = sum(
            zp[:, i : i + z.shape[1]] * p["conv_w"][i] for i in range(W)
        ) + p["conv_b"]
        hseq, h_last = _rglru(zc, p, cfg, cache["h"] if (cache and rs.mode == "full" and not rs.write_cache) else None)
        out = jnp.einsum("bsr,rd->bsd", (y * hseq.astype(h.dtype)), p["w_out"])
        new_cache = None
        if cache is not None and rs.write_cache:
            new_cache = {
                "h": h_last.astype(cache["h"].dtype),
                "conv": z[:, -(W - 1):].astype(cache["conv"].dtype),
            }
        return out, new_cache


# ---------------------------------------------------------------------------
# RWKV-6 (Finch) — time-mix (chunked linear attention) + channel-mix
# ---------------------------------------------------------------------------


def schema_rwkv(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    w = cfg.rwkv
    H = d // w.head_dim
    rank = w.ddlerp_rank
    return {
        "tm": {
            "maa_x": ParamDef((d,), ("embed",), init="zeros"),
            "maa": ParamDef((5, d), (None, "embed"), init="zeros"),   # w,k,v,r,g
            "A": ParamDef((d, 5 * rank), ("embed", None), scale=0.02),
            "B": ParamDef((5, rank, d), (None, None, "embed"), scale=0.02),
            "w0": ParamDef((d,), ("embed",), init="normal", scale=1.0),
            "w1": ParamDef((d, w.decay_rank), ("embed", None), scale=0.02),
            "w2": ParamDef((w.decay_rank, d), (None, "embed"), scale=0.02),
            "u": ParamDef((H, w.head_dim), ("heads", None), scale=0.5),
            "wr": ParamDef((d, d), ("embed", "rnn")),
            "wk": ParamDef((d, d), ("embed", "rnn")),
            "wv": ParamDef((d, d), ("embed", "rnn")),
            "wg": ParamDef((d, d), ("embed", "rnn")),
            "ln_w": ParamDef((d,), ("embed",), init="ones"),
            "ln_b": ParamDef((d,), ("embed",), init="zeros"),
            "wo": ParamDef((d, d), ("rnn", "embed"), scale=0.02),
        },
        "cm": {
            "maa_k": ParamDef((d,), ("embed",), init="zeros"),
            "maa_r": ParamDef((d,), ("embed",), init="zeros"),
            "wk": ParamDef((d, cfg.d_ff), ("embed", "ffn")),
            "wv": ParamDef((cfg.d_ff, d), ("ffn", "embed"), scale=0.02),
            "wr": ParamDef((d, d), ("embed", "rnn"), scale=0.02),
        },
    }


def cache_rwkv(cfg: ModelConfig, batch: int) -> dict:
    d = cfg.d_model
    hd = cfg.rwkv.head_dim
    H = d // hd
    return {
        "s": ParamDef((batch, H, hd, hd), ("batch", "heads", None, None), init="zeros", dtype="float32"),
        "tm_x": ParamDef((batch, d), ("batch", "embed"), init="zeros"),
        "cm_x": ParamDef((batch, d), ("batch", "embed"), init="zeros"),
    }


def _ddlerp(p: dict, x: jax.Array, x_prev: jax.Array):
    """Data-dependent token-shift interpolation -> (xw, xk, xv, xr, xg)."""
    dx = x_prev - x
    xx = x + dx * p["maa_x"]
    a = jnp.tanh(jnp.einsum("...d,dr->...r", xx, p["A"]))
    a5 = a.reshape(*a.shape[:-1], 5, p["B"].shape[1])     # (..., 5, rank)
    lora = jnp.einsum("...cr,crd->c...d", a5, p["B"])     # (5, ..., d)
    mix = p["maa"].reshape(5, *([1] * (x.ndim - 1)), x.shape[-1])
    outs = x[None] + dx[None] * (mix + lora)
    return tuple(outs[i] for i in range(5))


def _wkv_intra_3tensor(rc, kc, vc, clw, clw_prev, Lc):
    """Baseline intra-chunk form: explicit (t, s, D) decay tensor.  Exact but
    O(Lc^2 D) memory per chunk — the measured HBM-traffic bottleneck of
    rwkv6_3b (EXPERIMENTS.md §Perf hillclimb 3)."""
    diff = clw_prev[:, :, :, None, :] - clw[:, :, None, :, :]  # (B,H,t,s,D)
    tri = jnp.tril(jnp.ones((Lc, Lc), bool), k=-1)
    # mask BEFORE exp: masked entries get -inf so exp -> 0 with safe grads
    diff = jnp.where(tri[None, None, :, :, None], diff, -jnp.inf)
    A = jnp.einsum("bhtd,bhsd,bhtsd->bhts", rc, kc, jnp.exp(diff))
    return jnp.einsum("bhts,bhsv->bhtv", A, vc)


def _wkv_intra_subchunked(rc, kc, vc, clw, clw_prev, Lc, l):
    """GEMM-form intra-chunk (beyond-paper TPU adaptation, hillclimb 3).

    Split the chunk into ``ns = Lc/l`` subchunks.  All decay exponents are
    referenced to subchunk BOUNDARIES so every factor satisfies exp(<=0):
      r̂_t = r_t  · exp(clw_{t-1} − b_{I−1})   (t in subchunk I; b = boundary)
      k̂_s = k_s  · exp(b_J − clw_s)           (s in subchunk J)
      E_{I,J} = exp(b_{I−1} − b_J)            (per-d, J < I)
      A[t∈I, s∈J] = r̂_t · (k̂_s ⊙ E_{I,J})    — an MXU GEMM per (I, J<I)
    Only the l x l diagonal blocks need the explicit decay tensor: memory drops
    from O(Lc² D) to O(Lc l D + Lc²) per chunk and the off-diagonal work runs
    on the MXU.
    """
    B, H, _, D = rc.shape
    ns = Lc // l
    rs = lambda x: x.reshape(B, H, ns, l, D)
    r_s, k_s, v_s, clw_s, clwp_s = map(rs, (rc, kc, vc, clw, clw_prev))
    bnd = clw_s[:, :, :, -1, :]                     # (B,H,ns,D) subchunk ends

    # diagonal blocks: exact small 3-tensor
    diff = clwp_s[:, :, :, :, None, :] - clw_s[:, :, :, None, :, :]
    tri = jnp.tril(jnp.ones((l, l), bool), k=-1)
    diff = jnp.where(tri[None, None, None, :, :, None], diff, -jnp.inf)
    A_diag = jnp.einsum("bhntd,bhnsd,bhntsd->bhnts", r_s, k_s, jnp.exp(diff))
    out = jnp.einsum("bhnts,bhnsv->bhntv", A_diag, v_s)

    if ns > 1:
        # boundary-referenced factors (exponents <= 0 by monotonicity of clw)
        b_prev = jnp.concatenate(
            [jnp.zeros_like(bnd[:, :, :1]), bnd[:, :, :-1]], axis=2
        )                                            # b_{I-1}; b_{-1} = 0
        r_hat = r_s * jnp.exp(clwp_s - b_prev[:, :, :, None, :])
        k_hat = k_s * jnp.exp(bnd[:, :, :, None, :] - clw_s)
        for i in range(1, ns):
            # E[i, j<i, d] = exp(b_{i-1} - b_j)
            E = jnp.exp(b_prev[:, :, i : i + 1] - bnd[:, :, :i])   # (B,H,i,D)
            kh = k_hat[:, :, :i] * E[:, :, :, None, :]             # (B,H,i,l,D)
            scores = jnp.einsum("bhtd,bhjsd->bhtjs", r_hat[:, :, i], kh)
            out = out.at[:, :, i].add(
                jnp.einsum("bhtjs,bhjsv->bhtv", scores, v_s[:, :, :i])
            )
    return out.reshape(B, H, Lc, D)


def _wkv_chunked(
    r: jax.Array, k: jax.Array, v: jax.Array, logw: jax.Array, u: jax.Array,
    s0: jax.Array, chunk: int, subchunk: int = 0, unroll: bool = False,
):
    """Chunked RWKV-6 linear attention.

    r/k/v/logw: (B, H, T, D); u: (H, D); s0: (B, H, D, D) [key x value].
    Exact (log-space pairwise decay differences, all exponents <= 0).
    ``subchunk > 0`` selects the GEMM-form intra-chunk path (hillclimb 3).
    Returns (out (B,H,T,D), s_final).
    """
    B, H, T, D = r.shape
    Lc = min(chunk, T)
    pad = (-T) % Lc
    if pad:
        # end-padding is exact: k=0/v=0 add nothing, logw=0 (decay 1) leaves
        # the state untouched, r=0 rows are sliced away below.
        zp = lambda a: jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0)))
        r, k, v, logw = zp(r), zp(k), zp(v), zp(logw)
    Tp = T + pad
    n = Tp // Lc
    sub = subchunk if (subchunk and Lc % subchunk == 0 and Lc > subchunk) else 0

    def step(s, inp):
        rc, kc, vc, lwc = inp                    # (B, H, Lc, D)
        clw = jnp.cumsum(lwc, axis=2)            # inclusive cumulative log-decay
        clw_prev = clw - lwc                     # exclusive (cumlw_{t-1})
        # state contribution: r_t ⊙ exp(cumlw_{t-1}) against s
        r_dec = rc * jnp.exp(clw_prev)
        out_s = jnp.einsum("bhtd,bhdv->bhtv", r_dec, s)
        # intra-chunk
        if sub:
            out_i = _wkv_intra_subchunked(rc, kc, vc, clw, clw_prev, Lc, sub)
        else:
            out_i = _wkv_intra_3tensor(rc, kc, vc, clw, clw_prev, Lc)
        # bonus (current token)
        diag = jnp.einsum("bhtd,hd,bhtd->bht", rc, u, kc)
        out_b = diag[..., None] * vc
        # state update: s' = diag(exp(clw_L)) s + sum_s exp(clw_L - clw_s) k_s v_s^T
        last = clw[:, :, -1:, :]                 # (B,H,1,D)
        k_dec = kc * jnp.exp(last - clw)
        s_new = jnp.exp(last[:, :, 0])[:, :, :, None] * s + jnp.einsum(
            "bhsd,bhsv->bhdv", k_dec, vc
        )
        return s_new, out_s + out_i + out_b

    rs_ = lambda x: x.reshape(B, H, n, Lc, D).transpose(2, 0, 1, 3, 4)
    s_fin, outs = jax.lax.scan(
        step, s0, (rs_(r), rs_(k), rs_(v), rs_(logw)), unroll=unroll
    )
    out = outs.transpose(1, 2, 0, 3, 4).reshape(B, H, Tp, D)[:, :, :T]
    return out, s_fin


def apply_rwkv_tm(
    p: dict, h: jax.Array, cfg: ModelConfig, rs: RunState, cache: dict | None
) -> tuple[jax.Array, dict | None]:
    w = cfg.rwkv
    d = cfg.d_model
    H, D = d // w.head_dim, w.head_dim
    B = h.shape[0]

    if rs.mode == "decode":
        x = h[:, 0]
        x_prev = cache["tm_x"].astype(x.dtype)
        xw, xk, xv, xr, xg = _ddlerp(p, x, x_prev)
        logw = -jnp.exp(
            (p["w0"] + jnp.tanh(xw @ p["w1"]) @ p["w2"]).astype(jnp.float32)
        )
        r_ = (xr @ p["wr"]).reshape(B, H, D).astype(jnp.float32)
        k_ = (xk @ p["wk"]).reshape(B, H, D).astype(jnp.float32)
        v_ = (xv @ p["wv"]).reshape(B, H, D).astype(jnp.float32)
        g_ = jax.nn.silu(xg @ p["wg"])
        logw_h = logw.reshape(B, H, D)
        s = cache["s"].astype(jnp.float32)
        kv = jnp.einsum("bhd,bhv->bhdv", k_, v_)
        u = p["u"].astype(jnp.float32)
        out = jnp.einsum("bhd,bhdv->bhv", r_, s + u[None, :, :, None] * kv)
        s_new = jnp.exp(logw_h)[..., None] * s + kv
        o = out.reshape(B, d)
        o = L.layer_norm(o.reshape(B, H, D), jnp.zeros((D,), o.dtype)).reshape(B, d)
        o = o * p["ln_w"] + p["ln_b"]
        o = (o.astype(h.dtype) * g_) @ p["wo"]
        new_cache = {
            "s": s_new.astype(cache["s"].dtype),
            "tm_x": x.astype(cache["tm_x"].dtype),
            "cm_x": cache["cm_x"],
        }
        return o[:, None], new_cache

    # full mode
    S = h.shape[1]
    x_prev = jnp.pad(h, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    if cache is not None and not rs.write_cache:
        x_prev = x_prev.at[:, 0].set(cache["tm_x"].astype(h.dtype))
    xw, xk, xv, xr, xg = _ddlerp(p, h, x_prev)
    logw = -jnp.exp(
        (p["w0"] + jnp.tanh(xw @ p["w1"]) @ p["w2"]).astype(jnp.float32)
    )  # (B,S,d), <= 0
    to_h = lambda t: t.reshape(B, S, H, D).transpose(0, 2, 1, 3).astype(jnp.float32)
    r_, k_, v_ = to_h(xr @ p["wr"]), to_h(xk @ p["wk"]), to_h(xv @ p["wv"])
    g_ = jax.nn.silu(xg @ p["wg"])
    lw = to_h(logw)
    s0 = (
        cache["s"].astype(jnp.float32)
        if (cache is not None and not rs.write_cache)
        else jnp.zeros((B, H, D, D), jnp.float32)
    )
    out, s_fin = _wkv_chunked(
        r_, k_, v_, lw, p["u"].astype(jnp.float32), s0, w.chunk,
        subchunk=w.subchunk, unroll=cfg.scan_unroll,
    )
    o = out.transpose(0, 2, 1, 3)  # (B,S,H,D)
    o = L.layer_norm(o, jnp.zeros((D,), jnp.float32))
    o = o.reshape(B, S, d) * p["ln_w"] + p["ln_b"]
    o = (o.astype(h.dtype) * g_) @ p["wo"]
    new_cache = None
    if cache is not None and rs.write_cache:
        new_cache = {
            "s": s_fin.astype(cache["s"].dtype),
            "tm_x": h[:, -1].astype(cache["tm_x"].dtype),
            "cm_x": cache["cm_x"],
        }
    return o, new_cache


def apply_rwkv_cm(
    p: dict, h: jax.Array, cfg: ModelConfig, rs: RunState, cache: dict | None
) -> tuple[jax.Array, dict | None]:
    if rs.mode == "decode":
        x = h[:, 0]
        x_prev = cache["cm_x"].astype(x.dtype)
        new_cache = dict(cache)
        new_cache["cm_x"] = x.astype(cache["cm_x"].dtype)
    else:
        x = h
        x_prev = jnp.pad(h, ((0, 0), (1, 0), (0, 0)))[:, :-1]
        if cache is not None and not rs.write_cache:
            x_prev = x_prev.at[:, 0].set(cache["cm_x"].astype(h.dtype))
        new_cache = cache
        if cache is not None and rs.write_cache:
            new_cache = dict(cache)
            new_cache["cm_x"] = h[:, -1].astype(cache["cm_x"].dtype)
    xk = x + (x_prev - x) * p["maa_k"]
    xr = x + (x_prev - x) * p["maa_r"]
    v = jnp.square(jax.nn.relu(xk @ p["wk"])) @ p["wv"]
    out = jax.nn.sigmoid(xr @ p["wr"]) * v
    if rs.mode == "decode":
        return out[:, None] if out.ndim == 2 else out, new_cache
    return out, new_cache
