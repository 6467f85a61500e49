"""Model substrate: configs + schema-driven parameters with logical axes.

Every parameter is declared once in a *schema* — ``ParamDef(shape, axes,
init, scale)`` — from which we derive:

  * ``abstract_params``  — ``ShapeDtypeStruct`` tree (dry-run, no allocation);
  * ``init_params``      — concrete initialization (smoke tests, examples);
  * ``param_axes``       — logical-axis tree consumed by ``repro.sharding``.

Logical axis names (mapped to mesh axes by ``repro/sharding/rules.py``):
  "vocab"   — vocabulary dim (TP-sharded)
  "embed"   — residual-stream dim (FSDP-sharded along data when enabled)
  "heads"   — attention-head dim (TP)
  "kv_heads"— kv-head dim (TP if divisible, else replicated)
  "ffn"     — FFN hidden dim (TP)
  "experts" — MoE expert dim (EP over the model axis)
  "layers"  — stacked-scan group dim (never sharded)
  "lora"    — MLA latent dim (replicated)
  "rnn"     — recurrent-state channel dim (TP)
  None      — replicated
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Configs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MoECfg:
    n_routed: int
    n_shared: int
    top_k: int
    d_ff_expert: int
    first_dense_ff: int | None = None   # dense FFN width for prefix layers
    capacity_factor: float = 1.25
    router_dtype: str = "float32"
    norm_topk: bool = False


@dataclasses.dataclass(frozen=True)
class MLACfg:
    kv_lora: int = 512
    qk_nope: int = 128
    qk_rope: int = 64
    v_head: int = 128


@dataclasses.dataclass(frozen=True)
class YarnCfg:
    """DeepSeek's YaRN rope scaling (``rope_scaling`` of a checkpoint's
    config, type "yarn"): rotary frequencies blended between interpolated
    and extrapolated by a ramp over ``[beta_slow, beta_fast]`` rotations,
    and attention logits scaled by ``mscale(mscale_all_dim) ** 2``."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class RnnCfg:
    """RG-LRU recurrent block (Griffin / RecurrentGemma)."""

    d_rnn: int = 0            # 0 => same as d_model
    conv_width: int = 4
    c: float = 8.0            # decay sharpness constant
    block_width_divisor: int = 1


@dataclasses.dataclass(frozen=True)
class RwkvCfg:
    head_dim: int = 64
    chunk: int = 16           # chunked linear-attention chunk length
    subchunk: int = 0         # >0: GEMM-form intra-chunk (EXPERIMENTS §Perf h3)
    ddlerp_rank: int = 32     # low-rank data-dependent interpolation (token shift)
    decay_rank: int = 64


@dataclasses.dataclass(frozen=True)
class FrontendCfg:
    """Stubbed modality frontend: input_specs provides precomputed embeddings."""

    kind: str                 # "vision" | "audio"
    d_in: int                 # per-position feature dim delivered by the stub
    n_tokens: int             # number of frontend positions (patches / frames)
    cross_gated: bool = True  # tanh-gated cross-attn (llama-3.2-vision style)
    enc_layers: int = 0       # encoder depth (whisper-style enc-dec only)


@dataclasses.dataclass(frozen=True)
class MoLeCfg:
    """MoLe secure-delivery feature flags (DESIGN.md §4)."""

    enabled: bool = False
    mode: str = "token"       # "token" (vocab permutation) | "embedding" (block-diag)
    kappa: int = 1
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str               # dense | moe | hybrid | ssm | vlm | audio
    vocab: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    block_pattern: tuple[str, ...]        # layer kinds, scanned n_groups times
    n_groups: int
    prefix_pattern: tuple[str, ...] = ()  # unscanned leading layers
    suffix_pattern: tuple[str, ...] = ()  # unscanned trailing layers
    norm: str = "rmsnorm"                 # rmsnorm | layernorm
    act: str = "swiglu"                   # swiglu | geglu | gelu
    parallel_block: bool = False          # command-r style attn+ffn in parallel
    post_norm: bool = False               # gemma2 extra post-sublayer norms
    rope_theta: float = 10_000.0
    rope_scaling: YarnCfg | None = None   # YaRN (DeepSeek-V2); None = plain
    sliding_window: int | None = None
    attn_scale: float | None = None       # None => head_dim ** -0.5
    attn_softcap: float | None = None
    final_softcap: float | None = None
    scale_embedding: bool = False
    tie_embeddings: bool = False
    qkv_bias: bool = False
    moe: MoECfg | None = None
    mla: MLACfg | None = None
    rnn: RnnCfg | None = None
    rwkv: RwkvCfg | None = None
    frontend: FrontendCfg | None = None
    mole: MoLeCfg = dataclasses.field(default_factory=MoLeCfg)
    dtype: str = "bfloat16"               # activation dtype
    param_dtype: str = "bfloat16"
    flash_block_kv: int = 1024            # flash-scan KV chunk
    dense_attn_max_seq: int = 1024        # use dense attention at/below this
    scan_unroll: bool = False             # unroll layer scans (analysis passes:
                                          # XLA:CPU cost_analysis counts while
                                          # bodies once; see launch/dryrun.py)
    fused_ce: bool = True                 # chunked softmax-CE (never builds
                                          # (B,S,V) logits; §Perf beyond-paper 4)
    source: str = ""                      # provenance note

    @property
    def n_layers(self) -> int:
        return (
            len(self.prefix_pattern)
            + self.n_groups * len(self.block_pattern)
            + len(self.suffix_pattern)
        )

    @property
    def adtype(self):
        return jnp.dtype(self.dtype)

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    def layer_kinds(self) -> list[str]:
        return (
            list(self.prefix_pattern)
            + list(self.block_pattern) * self.n_groups
            + list(self.suffix_pattern)
        )

    def param_count(self) -> int:
        """Total parameter count (from the schema, exact)."""
        from .stack import model_schema  # local import to avoid cycle

        schema = model_schema(self)
        return sum(
            int(np.prod(d.shape)) for d in jax.tree.leaves(
                schema, is_leaf=lambda x: isinstance(x, ParamDef)
            )
        )

    def active_param_count(self) -> int:
        """Active params per token (MoE: shared + top_k routed experts)."""
        if self.moe is None:
            return self.param_count()
        total = self.param_count()
        m = self.moe
        n_moe_layers = sum(1 for k in self.layer_kinds() if k.endswith("_moe"))
        per_expert = 3 * self.d_model * m.d_ff_expert
        inactive = n_moe_layers * (m.n_routed - m.top_k) * per_expert
        return total - inactive


# ---------------------------------------------------------------------------
# Schema-driven parameters
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    init: str = "normal"     # normal | zeros | ones | neg_ones | embed
    scale: float | None = None  # None => 1/sqrt(fan_in) for normal
    dtype: str | None = None    # None => caller's default dtype

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _is_def(x: Any) -> bool:
    return isinstance(x, ParamDef)


def abstract_params(schema: Any, dtype) -> Any:
    return jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, jnp.dtype(d.dtype or dtype)),
        schema, is_leaf=_is_def,
    )


def param_axes(schema: Any) -> Any:
    return jax.tree.map(lambda d: d.axes, schema, is_leaf=_is_def)


def init_params(key: jax.Array, schema: Any, dtype) -> Any:
    leaves, treedef = jax.tree.flatten(schema, is_leaf=_is_def)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, d in zip(keys, leaves):
        dt = jnp.dtype(d.dtype or dtype)
        if d.init == "zeros":
            v = jnp.zeros(d.shape, dt)
        elif d.init == "ones":
            v = jnp.ones(d.shape, dt)
        elif d.init == "neg_ones":
            v = -jnp.ones(d.shape, dt)
        else:
            if d.init == "embed":
                scale = 1.0 if d.scale is None else d.scale
            else:
                fan_in = d.shape[-2] if len(d.shape) >= 2 else d.shape[-1]
                scale = (1.0 / math.sqrt(fan_in)) if d.scale is None else d.scale
            v = _normal(k, d.shape, scale, dt)
        out.append(v)
    return jax.tree.unflatten(treedef, out)


# Leaves above this many elements are drawn block by block: each block by one
# program straight into the leaf's buffer, so the device never holds more
# than the leaf and one block's float32 draw.
_DRAW_SLICE = 2 ** 26


def _normal(key: jax.Array, shape: tuple, scale: float, dt) -> jax.Array:
    """``N(0, scale^2)`` in ``dt``; a large leaf drawn in blocks of at most
    ``_DRAW_SLICE`` elements (each from its own key), one block at a time,
    so that a full-size layer stack fits the device while it is drawn and
    the device's peak does not depend on how far dispatch runs ahead.  A
    leaf within one block keeps its single draw: its temporaries are one
    leaf's, and the fused block program rounds the scaling differently, so
    drawing it that way would change every seeded model's weights."""
    n = int(np.prod(shape))
    if len(shape) < 2 or n <= _DRAW_SLICE:
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dt)
    # Blocks along the first axis whose trailing extent fits a block.
    axis = next(a for a in range(len(shape))
                if int(np.prod(shape[a + 1:])) <= _DRAW_SLICE)
    step = max(1, _DRAW_SLICE // int(np.prod(shape[axis + 1:])))
    out = jnp.zeros(shape, dt)
    starts = np.ndindex(*shape[:axis], -(-shape[axis] // step))
    for i, idx in enumerate(starts):
        start = (*idx[:-1], idx[-1] * step) + (0,) * (len(shape) - axis - 1)
        block = ((1,) * axis + (min(step, shape[axis] - start[axis]),)
                 + tuple(shape[axis + 1:]))
        out = _draw_into(out, jax.random.fold_in(key, i),
                         tuple(np.int32(x) for x in start), np.float32(scale),
                         block=block)
        out.block_until_ready()
    return out


@partial(jax.jit, static_argnames=("block",), donate_argnums=(0,))
def _draw_into(out, key, start, scale, *, block):
    draw = jax.random.normal(key, block, jnp.float32) * scale
    return jax.lax.dynamic_update_slice(out, draw.astype(out.dtype), start)


def stacked(n: int, d: ParamDef) -> ParamDef:
    """Add a leading scanned-layers dim to a ParamDef."""
    return ParamDef((n, *d.shape), ("layers", *d.axes), d.init, d.scale)


def map_stacked(n: int, schema: Any) -> Any:
    return jax.tree.map(lambda d: stacked(n, d), schema, is_leaf=_is_def)
