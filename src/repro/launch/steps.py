"""Step builders: train / prefill / decode, with microbatch gradient
accumulation, remat, and pinned in/out shardings for AOT lowering.

These are the functions the dry-run lowers and the drivers execute; they are
pure (params/opt/caches in -> out) so checkpointing and restart are trivial.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from ..kernels.ops import aug_embed_rows_grouped, lm_head_rows_grouped
from ..models import blocks as B
from ..models import layers as L
from ..models import stack as S
from ..models.api import Model
from ..models.base import init_params
from ..optim import adamw


@dataclasses.dataclass(frozen=True)
class TrainHParams:
    optimizer: adamw.AdamWConfig = dataclasses.field(default_factory=adamw.AdamWConfig)
    microbatch: int | None = None   # per-DEVICE-GROUP microbatch count: None=1 shot
    remat: bool = True


def _split_micro(batch: dict, n_micro: int) -> dict:
    def rs(x):
        b = x.shape[0]
        assert b % n_micro == 0, (b, n_micro)
        return x.reshape(n_micro, b // n_micro, *x.shape[1:])
    return {k: rs(v) for k, v in batch.items()}


def make_train_step(model: Model, hp: TrainHParams):
    """(params, opt_state, batch, step) -> (params, opt_state, metrics)."""

    def loss_fn(params, mb):
        return model.loss(params, mb, remat=hp.remat)

    # analysis: jit-step
    def train_step(params, opt_state, batch):
        n_micro = hp.microbatch or 1
        if n_micro > 1:
            micro = _split_micro(batch, n_micro)

            def body(acc, mb):
                gsum, lsum = acc
                loss, g = jax.value_and_grad(loss_fn)(params, mb)
                gsum = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), gsum, g
                )
                return (gsum, lsum + loss), None

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            (gsum, lsum), _ = jax.lax.scan(body, (g0, jnp.zeros(())), micro)
            grads = jax.tree.map(lambda g: g / n_micro, gsum)
            loss = lsum / n_micro
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)

        params, opt_state, metrics = adamw.apply(
            hp.optimizer, params, grads, opt_state
        )
        metrics = dict(metrics, loss=loss)
        return params, opt_state, metrics

    return train_step


def make_prefill_step(model: Model):
    """(params, batch, caches) -> (last-token logits, caches)."""

    # analysis: jit-step
    def prefill_step(params, batch, caches):
        return model.prefill_with_cache(params, batch, caches)

    return prefill_step


def make_decode_step(model: Model):
    """(params, token (B,1), t scalar, caches) -> (logits (B,1,V), caches)."""

    # analysis: jit-step
    def decode_step(params, token, t, caches):
        return model.decode(params, token, t, caches)

    return decode_step


# Mixers the row-batched serving steps run: attention with a KV cache (run
# per row) and MLA (batched over rows, a position per row).
_ROW_MIXERS = ("attn", "global", "local", "mla")


def rows_supported(cfg) -> bool:
    """Whether the row-batched serving steps run this model: a plain LM of
    pre-norm blocks whose mixers are attention or MLA."""
    return (
        cfg.family != "audio" and cfg.frontend is None
        and not cfg.parallel_block
        and all(B.mixer_of(k) in _ROW_MIXERS for k in cfg.layer_kinds())
    )


def _check_plain_lm(model: Model, what: str) -> None:
    if not rows_supported(model.cfg):
        cfg = model.cfg
        raise ValueError(
            f"{what} serves plain LMs of attention or MLA blocks only "
            f"(family={cfg.family!r}, kinds={sorted(set(cfg.layer_kinds()))}, "
            f"frontend={'set' if cfg.frontend else None}); use the "
            f"per-tenant prefill/decode steps for other models"
        )


def _ffn(p, n, cfg, kind, backend, keep):
    """A block's FFN over (N, S, d): ``(out, experts hit or None)``; the
    tokens outside ``keep`` (N * S,) route to no expert."""
    if B.ffn_of(kind) != "moe":
        return B.apply_ffn(p, n, cfg), None
    y, hit = B.moe_dropless(p, n.reshape(-1, n.shape[-1]), cfg,
                            backend=backend, keep=keep)
    return y.reshape(n.shape), hit


def _residual(p, h, a, cfg, kind, backend, keep):
    """The rest of a pre-norm block after its mixer output ``a``."""
    if cfg.post_norm:
        a = L.norm(a, p["post_norm1"], cfg.norm)
    h = h + a
    fo, hit = _ffn(p["ffn"], L.norm(h, p["norm2"], cfg.norm), cfg, kind,
                   backend, keep)
    if cfg.post_norm:
        fo = L.norm(fo, p["post_norm2"], cfg.norm)
    return h + fo, hit


def layer_params(params, cfg, *, free: bool = False) -> dict:
    """The trunk's weights a layer at a time, as the row-batched serving
    steps take them: ``{"layers": [...], "final_norm": ...}``, the scanned
    groups' stacks split into one dict per layer.  A step then reads each
    layer's arrays whole: nothing slices a stack on every call (a kernel's
    operand cannot be a slice fused into it, so a slice is a copy).  With
    ``free``, each stacked array is deleted once split, so the device holds
    the trunk once, not twice, while it is split."""
    per_block = []
    for i in range(len(cfg.block_pattern)):
        leaves, tree = jax.tree.flatten(params["blocks"][f"b{i}"])
        parts = []
        for a in leaves:
            parts.append([a[g] for g in range(cfg.n_groups)])
            if free:
                # Once its slices exist: the device then holds at most one
                # stack twice, however far dispatch runs ahead.
                jax.block_until_ready(parts[-1])
                a.delete()
        per_block.append([tree.unflatten([p[g] for p in parts])
                          for g in range(cfg.n_groups)])
    layers = list(params.get("prefix", []))
    layers += [blocks[g] for g in range(cfg.n_groups) for blocks in per_block]
    layers += list(params.get("suffix", []))
    return {"layers": layers, "final_norm": params["final_norm"]}


def layer_caches(model: Model, batch: int, max_len: int) -> list:
    """One empty cache per layer, in layer order, for ``batch`` sequences."""
    cfg = model.cfg
    return [
        init_params(jax.random.key(0),
                    S.block_cache_schema(cfg, kind, batch, max_len), cfg.adtype)
        for kind in cfg.layer_kinds()
    ]


def _walk(params, h, cfg, caches, block):
    """Every layer in order, unrolled: ``block(p, h, kind, cache) -> (h,
    cache out, experts hit or None)``.  Returns ``(h, caches out, hits)``,
    the hits an int32 vector with one entry per MoE layer."""
    out, hits = [], []
    for kind, p, c in zip(cfg.layer_kinds(), params["layers"], caches):
        h, nc, hit = block(p, h, kind, c)
        out.append(nc)
        if hit is not None:
            hits.append(hit)
    return h, out, jnp.stack(hits) if hits else jnp.zeros((0,), jnp.int32)


def _row_logits(h, sidx, aug_heads, cfg, params, backend, keep=None):
    """Final norm and each row's Aug-head: ``(argmax (R,), its logit (R,))``,
    the logits in the tenant's morphed vocabulary order (rows outside
    ``keep`` read no head)."""
    h = L.norm(h, params["final_norm"], cfg.norm)
    logits = lm_head_rows_grouped(h, sidx, aug_heads, backend=backend,
                                  keep=keep)
    logits = L.softcap(logits.astype(jnp.float32), cfg.final_softcap)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return tok, jnp.take_along_axis(logits, tok[:, None], axis=-1)[:, 0]


def make_row_prefill_step(model: Model, backend: str | None = None):
    """Single-sequence prefill against *delivered* per-tenant artifacts.

    ``(params, aug_embeds (S, V, d), aug_heads (S, d, V), slot (), tokens
    (1, Lb), length (), caches) -> (first token (1,) int32, its logit (1,),
    experts hit per MoE layer, caches)``

    The continuous-batching admission step: ``params`` are the shared
    (tenant-independent) trunk weights a layer at a time
    (:func:`layer_params`), ``caches`` one B=1 cache per layer
    (:func:`layer_caches`), and the tenant's fused AugE table
    and Aug-head are read from the stacks through the traced ``slot`` —
    one compiled graph per prompt bucket ``Lb`` serves every tenant.  The
    prompt is right-padded to ``Lb``; causal attention keeps the padding out
    of the true positions, the padding routes to no expert, and the first
    token comes from position ``length - 1``.  Only that position reaches
    the head (norm and head are per-position maps, so this equals slicing
    the full-sequence logits).
    """
    _check_plain_lm(model, "make_row_prefill_step")
    cfg = model.cfg

    def block(p, h, kind, cache, keep):
        rs = B.RunState(mode="full", write_cache=True)
        a, nc = S.apply_mixer(p["mix"], L.norm(h, p["norm1"], cfg.norm), cfg,
                              kind, rs, cache)
        h, hit = _residual(p, h, a, cfg, kind, backend, keep)
        return h, nc, hit

    # analysis: jit-step
    def row_prefill_step(params, aug_embeds, aug_heads, slot, tokens, length,
                         caches):
        h = aug_embeds[slot, tokens[0]][None].astype(cfg.adtype)
        if cfg.scale_embedding:
            h = h * jnp.asarray(cfg.d_model ** 0.5, h.dtype)
        keep = jnp.arange(tokens.shape[1]) < length
        h, caches, hits = _walk(
            params, h, cfg, caches,
            lambda p, h, kind, c: block(p, h, kind, c, keep),
        )
        last = jax.lax.dynamic_index_in_dim(h[0], length - 1, keepdims=True)
        tok, logit = _row_logits(last, slot[None], aug_heads, cfg, params,
                                 backend)
        return tok, logit, hits, caches

    return row_prefill_step


def make_batched_decode_step(model: Model, backend: str | None = None):
    """One greedy decode step for a whole cross-tenant row batch.

    ``(params, aug_embeds (S, V, d), aug_heads (S, d, V), sidx (R,),
    tokens (R,), t (R,), live (R,) bool, caches) -> (next tokens (R,)
    int32, their logits (R,), experts hit per MoE layer, caches)``

    Each row ``r`` is one tenant sequence at its own position ``t[r]``: its
    token embeds through slot ``sidx[r]``'s AugE table
    (:func:`~repro.kernels.ops.aug_embed_rows_grouped`), the trunk runs over
    all rows at once — MLA batched with a position per row, other mixers per
    row against their B=1 caches, every FFN over the whole row batch (the
    MoE dropless, routing all R tokens together) — and the logits come from
    the stacked Aug-heads grouped by slot
    (:func:`~repro.kernels.ops.lm_head_rows_grouped`).  ``params`` come a
    layer at a time (:func:`layer_params`); ``caches`` are
    :func:`row_caches`'.  MLA reads its cache and writes each row's new
    entry once after the walk.  Rows outside ``live`` route to no expert and
    read no Aug-head; their outputs are garbage.  Every array keeps its
    shape as sequences join and leave, so the step never retraces on churn.
    """
    _check_plain_lm(model, "make_batched_decode_step")
    cfg = model.cfg

    def block(p, h, kind, cache, t, live):
        n = L.norm(h, p["norm1"], cfg.norm)
        if B.mixer_of(kind) == "mla":
            a, ckv, kr = B.mla_decode_rows(p["mix"], n, cfg, t,
                                           cache["ckv"], cache["kr"])
            new = {"ckv": ckv, "kr": kr}
        else:
            def one(n_r, t_r, c_r):
                rs = B.RunState(mode="decode", t=t_r)
                a_r, c_r = S.apply_mixer(p["mix"], n_r[None], cfg, kind, rs, c_r)
                return a_r[0], c_r

            a, new = jax.vmap(one)(n, t, cache)
        h, hit = _residual(p, h, a, cfg, kind, backend, live)
        return h, new, hit

    def write(cache, new, kind, t):
        if B.mixer_of(kind) != "mla":
            return new
        rows = jnp.arange(t.shape[0])
        return {k: cache[k].at[rows, t].set(new[k]) for k in new}

    # analysis: jit-step
    def batched_decode_step(params, aug_embeds, aug_heads, sidx, tokens, t,
                            live, caches):
        h0 = aug_embed_rows_grouped(tokens, sidx, aug_embeds, backend=backend)
        h0 = h0.astype(cfg.adtype)
        if cfg.scale_embedding:
            h0 = h0 * jnp.asarray(cfg.d_model ** 0.5, h0.dtype)
        h, new, hits = _walk(
            params, h0[:, None, :], cfg, caches,
            lambda p, h, kind, c: block(p, h, kind, c, t, live),
        )
        out = [write(c, n, k, t)
               for c, n, k in zip(caches, new, cfg.layer_kinds())]
        tok, logit = _row_logits(h[:, 0], sidx, aug_heads, cfg, params,
                                 backend, live)
        return tok, logit, hits, out

    return batched_decode_step


def row_caches(model: Model, rows: int, max_len: int) -> list:
    """The batched decode step's caches, per layer: an MLA layer's latent
    cache with one entry per row (MLA decodes the rows as one batch), any
    other mixer's B=1 cache per row on a leading row axis (it decodes each
    row on its own)."""
    cfg = model.cfg
    return [
        jax.tree.map(lambda l: jnp.zeros((rows, *l.shape[1:]), l.dtype), c)
        if B.mixer_of(kind) == "mla"
        else jax.tree.map(lambda l: jnp.stack([l] * rows), c)
        for kind, c in zip(cfg.layer_kinds(), layer_caches(model, 1, max_len))
    ]


def scatter_row(caches, one, row):
    """Write the per-layer B=1 caches ``one`` (a prefill's) into row ``row``
    of :func:`row_caches`' layout."""
    return [jax.tree.map(lambda b, s: b.at[row].set(s.reshape(b.shape[1:])),
                         c, o)
            for c, o in zip(caches, one)]
