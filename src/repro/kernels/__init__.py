"""Pallas TPU kernels for MoLe's compute hot-spots (validated interpret=True).

  aug_gemm    — tiled GEMM: developer-side Aug-Conv forward T @ C^{ac}
                (eq. 5), and provider-side morphing (eq. 2-4) on rows cut
                to the core width
  grouped     — the slot-indexed grouped GEMM (``tiled_group_gemm``): the
                delivery steps' morph and Aug-Conv, the routed experts and
                the Aug-head, each group's matrix read in place from its
                stack via scalar-prefetched index maps
  wkv6        — chunked RWKV-6 linear-attention scan (rwkv6_3b long-context)

Each kernel has a pure-jnp oracle in ``ref.py``; ``ops.py`` holds the jit'd
public wrappers and is the one place that chooses what runs: the kernel on
the Pallas backends, its reference on ``jnp`` (``dispatch.py`` resolves the
backend name).
"""
from .dispatch import BACKENDS, resolve_backend
from .ops import (
    aug_conv_forward,
    aug_conv_forward_grouped,
    aug_embed_grouped,
    morph_rows,
    morph_rows_grouped,
    token_morph_grouped,
)
from .wkv6 import wkv6_chunked
from . import ref

__all__ = [
    "BACKENDS",
    "resolve_backend",
    "aug_conv_forward",
    "aug_conv_forward_grouped",
    "aug_embed_grouped",
    "morph_rows",
    "morph_rows_grouped",
    "token_morph_grouped",
    "wkv6_chunked",
    "ref",
]
