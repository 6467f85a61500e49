"""Kernel backend selection.

Three backends implement the same math for every public kernel entry point:

  ``pallas``     compiled Pallas kernels (``interpret=False``) — real TPU.
  ``interpret``  Pallas kernels in interpret mode — CPU validation of the
                 kernel bodies themselves (slow: the grid runs in Python).
  ``jnp``        pure-jnp reference (``kernels.ref``) — XLA-fused; the CPU
                 path.

This module only names and resolves the backend; ``kernels.ops`` decides
what each entry point runs for it, one implementation per backend.

Resolution order for ``resolve_backend(None)``:

  1. ``REPRO_KERNEL_BACKEND`` env var if set to one of the names above;
  2. auto: ``pallas`` when a TPU backend is active, else ``jnp``.
"""
from __future__ import annotations

import os

import jax

BACKENDS = ("pallas", "interpret", "jnp")


def resolve_backend(backend: str | None = None) -> str:
    """Resolve an explicit/env/auto backend choice to one of ``BACKENDS``."""
    if backend is None:
        backend = os.environ.get("REPRO_KERNEL_BACKEND", "auto").lower()
    if backend in BACKENDS:
        return backend
    if backend not in ("auto", ""):
        raise ValueError(
            f"unknown kernel backend {backend!r}; expected one of {BACKENDS} or 'auto'"
        )
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def pallas_interpret(backend: str) -> bool:
    """Whether a resolved pallas-family backend runs in interpret mode."""
    return backend == "interpret"
