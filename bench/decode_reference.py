"""The plain reference the decode lane's sample is held to: DeepSeek-V2's
forward pass in float32 under ``jax.default_matmul_precision("highest")``,
teacher-forced over each sampled prompt and the ids the server generated.

Written from the published architecture (and the configuration file's
numbers), apart from the program's own model code:

* MLA without query compression: queries split into ``qk_nope_head_dim``
  and ``qk_rope_head_dim`` parts, keys and values decompressed from the
  RMS-normed latent, one rotary key shared by the heads, causal softmax;
* YaRN rope from ``rope_scaling`` and the softmax scale
  ``qk_head_dim ** -0.5 * mscale(factor, mscale_all_dim) ** 2``;
* the first ``first_k_dense_replace`` layers a dense SwiGLU FFN, the others
  softmax-routed greedy top-``num_experts_per_tok`` experts, not
  renormalized, every expert computed on every token and masked, plus the
  shared experts.

No cache, kernels or batching of decode steps.  The sequences run padded to
one length in one batch (causal attention keeps the padding out of every
true position), one layer's weights on the device at a time.  Departures,
each a relabelling that changes no compared number: RMSNorm scales by
``1 + w`` (the program's parameterization); rope rotates the halves of the
rotary part, where the checkpoint's weights rotate interleaved pairs; the
weights are the seeded ones the server drew, taken from it before it closed.
"""
from __future__ import annotations

import math

import numpy as np

F32 = np.float32


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def rope_tables(config: dict, length: int) -> tuple[np.ndarray, np.ndarray]:
    """cos and sin, (length, qk_rope_head_dim // 2), of YaRN's rope."""
    dim, base = config["qk_rope_head_dim"], float(config["rope_theta"])
    y = config.get("rope_scaling")
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inv, m = extra, 1.0
    if y:
        inter = extra / y["factor"]
        orig = y["original_max_position_embeddings"]

        def corr(rot):
            return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

        lo = max(math.floor(corr(y["beta_fast"])), 0)
        hi = min(math.ceil(corr(y["beta_slow"])), dim - 1)
        hi = hi + 0.001 if lo == hi else hi
        keep = 1.0 - np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0, 1)
        inv = inter * (1 - keep) + extra * keep
        m = _mscale(y["factor"], y["mscale"]) / _mscale(y["factor"],
                                                       y["mscale_all_dim"])
    ang = np.arange(length)[:, None] * inv[None]
    return (np.cos(ang) * m).astype(F32), (np.sin(ang) * m).astype(F32)


def softmax_scale(config: dict) -> float:
    s = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]) ** -0.5
    y = config.get("rope_scaling")
    if y and y.get("mscale_all_dim"):
        s *= _mscale(y["factor"], y["mscale_all_dim"]) ** 2
    return s


def layer_params(params: dict, config: dict) -> list[tuple[bool, dict]]:
    """``(is_moe, weights)`` per layer, host arrays, from the server's trunk
    (one dict a layer): the first ``first_k_dense_replace`` layers dense."""
    layers = params["layers"]
    assert len(layers) == config["num_hidden_layers"], len(layers)
    dense = config["first_k_dense_replace"]
    return [(i >= dense, p) for i, p in enumerate(layers)]


def _functions(config: dict):
    """The jitted pieces: one layer (dense or MoE) over a padded batch, and
    the logits' statistics at chosen positions."""
    import jax
    import jax.numpy as jnp

    nope = config["qk_nope_head_dim"]
    k_top = config["num_experts_per_tok"]
    scale = softmax_scale(config)
    eps = config["rms_norm_eps"]

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1 + w)

    def rope(x, cos, sin):
        h = x.shape[-1] // 2
        a, b = x[..., :h], x[..., h:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    def attention(p, x, cos, sin):                          # x (N, L, d)
        L = x.shape[1]
        q = jnp.einsum("nld,dhk->nlhk", x, p["wq"])
        q_nope = q[..., :nope]
        q_pe = rope(q[..., nope:], cos[:, None], sin[:, None])
        ckv = rms(x @ p["w_dkv"], p["kv_norm"])
        k_pe = rope(x @ p["w_kr"], cos, sin)                # (N, L, r)
        k_nope = jnp.einsum("nlc,chk->nlhk", ckv, p["w_uk"])
        v = jnp.einsum("nlc,chv->nlhv", ckv, p["w_uv"])
        s = (jnp.einsum("nqhk,nthk->nhqt", q_nope, k_nope)
             + jnp.einsum("nqhr,ntr->nhqt", q_pe, k_pe)) * scale
        s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -jnp.inf)
        o = jnp.einsum("nhqt,nthv->nqhv", jax.nn.softmax(s, -1), v)
        return jnp.einsum("nqhv,hvd->nqd", o, p["wo"])

    def swiglu(x, wg, wu, wd):
        return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd

    def moe(p, x):                                           # x (T, d)
        probs = jax.nn.softmax(x @ p["router"], -1)
        top_p, top_i = jax.lax.top_k(probs, k_top)
        gate = jnp.zeros_like(probs).at[
            jnp.arange(x.shape[0])[:, None], top_i].set(top_p)

        def expert(y, e):
            w = jax.tree.map(lambda a: a[e], (p["wg"], p["wu"], p["wd"]))
            return y + gate[:, e, None] * swiglu(x, *w), None

        y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                            jnp.arange(p["wg"].shape[0]))
        s = p["shared"]
        return y + swiglu(x, s["wi_gate"], s["wi_up"], s["wo"])

    def layer(p, x, cos, sin, is_moe):
        with jax.default_matmul_precision("highest"):
            x = x + attention(p["mix"], rms(x, p["norm1"]), cos, sin)
            n = rms(x, p["norm2"])
            if is_moe:
                f = moe(p["ffn"], n.reshape(-1, n.shape[-1])).reshape(n.shape)
            else:
                f = swiglu(n, p["ffn"]["wi_gate"], p["ffn"]["wi_up"],
                           p["ffn"]["wo"])
            return x + f

    def stats(h, norm, head, ids):                          # h (P, d)
        with jax.default_matmul_precision("highest"):
            logits = rms(h, norm) @ head                     # (P, V)
        picked = jnp.take_along_axis(logits, ids[:, None], -1)[:, 0]
        return picked, logits.max(-1), logits.std(-1)

    return (jax.jit(layer, static_argnames=("is_moe",)), jax.jit(stats))


def logit_stats(secrets: dict, config: dict, sequences: list) -> dict:
    """For each ``(prompt, served ids)`` in ``sequences``, the reference's
    logits at every generated position: the served id's logit, the largest
    logit and the logits' standard deviation, each concatenated over the
    sequences in order."""
    import jax
    import jax.numpy as jnp

    layer, stats = _functions(config)
    texts = [np.concatenate([p, s[:-1]]).astype(np.int32) for p, s in sequences]
    L = -(-max(t.size for t in texts) // 128) * 128
    tokens = np.zeros((len(texts), L), np.int32)
    for i, t in enumerate(texts):
        tokens[i, : t.size] = t
    cos, sin = (jnp.asarray(a) for a in rope_tables(config, L))
    x = jnp.asarray(np.asarray(secrets["embed"], F32)[tokens])
    for is_moe, p in layer_params(secrets["params"], config):
        dev = jax.tree.map(lambda a: jnp.asarray(np.asarray(a, F32)), p)
        x = layer(dev, x, cos, sin, is_moe=is_moe)
        del dev
    rows = np.concatenate([np.arange(p.size - 1, p.size - 1 + s.size)
                           + i * L for i, (p, s) in enumerate(sequences)])
    ids = np.concatenate([s for _, s in sequences]).astype(np.int32)
    h = x.reshape(-1, x.shape[-1])[jnp.asarray(rows)]
    picked, top, std = stats(
        h, jnp.asarray(np.asarray(secrets["params"]["final_norm"], F32)),
        jnp.asarray(np.asarray(secrets["head"], F32)), jnp.asarray(ids))
    return {"picked": np.asarray(picked), "max": np.asarray(top),
            "std": np.asarray(std)}
