"""Activation sharding hints, safe under any (or no) mesh context.

``hint(x, *axes)`` applies ``with_sharding_constraint`` with the given
per-dim mesh-axis names, silently dropping names absent from the ambient mesh
(or doing nothing when tracing without a mesh).  "dp" expands to whichever of
("pod", "data") exist.  Divisibility is checked so hints never break a shape.
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import PartitionSpec as P


def _mesh():
    m = jax.sharding.get_abstract_mesh()
    return m if m.axis_names else None


def hint(x, *axes):
    m = _mesh()
    if m is None:
        return x
    names = set(m.axis_names)
    sizes = dict(zip(m.axis_names, m.axis_sizes))
    parts = []
    for dim, a in zip(x.shape, axes):
        if a == "dp":
            a = tuple(n for n in ("pod", "data") if n in names)
            a = a if a else None
        if a is None:
            parts.append(None)
            continue
        tup = (a,) if isinstance(a, str) else tuple(a)
        if not all(t in names for t in tup):
            parts.append(None)
            continue
        size = int(np.prod([sizes[t] for t in tup]))
        if size == 0 or dim % size != 0:
            parts.append(None)
            continue
        parts.append(tup[0] if len(tup) == 1 else tup)
    parts += [None] * (x.ndim - len(parts))
    return jax.lax.with_sharding_constraint(x, P(*parts))
