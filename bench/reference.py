"""The plain reference that decides ``correct``, and its control.

What a delivery returns, for tenant ``t`` and images ``x``, is the first
layer of the tenant's model, ``conv(x, K_t)``, with its output channels in
the order of the tenant's secret permutation (paper eq. 5).  The reference
computes that convolution here on the host in float64 -- ``numpy`` only, no
JAX and nothing of the program -- from the tenant's kernels, which it draws
from the seed by the recipe the front door documents
(``launch/server.developer_kernels``, copied below).

The permutation is the provider's secret, drawn inside the program from the
operating system's entropy, so the check takes it from the provider's own
record (``DataProvider._perm``, as ``chip_smoke.py`` does) and holds every
sampled request of the tenant to ``conv(x, K_t)[:, perm_t]``.  A permutation
that is not one, or is the identity, is counted apart: with it the guarantee
the system exists for is gone, whatever the values.  A wrong value, a wrong
tenant's secrets, or channels in any order but the provider's all show as a
large error.

The control is this reference in the precision below the configuration's
float32 at ``highest``: three bfloat16 passes (``high``), emulated exactly by
splitting both operands into bfloat16 high and low parts.
"""
from __future__ import annotations

import numpy as np


def developer_kernels(geom: dict, tenants: int, seed: int) -> list[np.ndarray]:
    """Each tenant's conv kernels ``(alpha, beta, p, p)``, in tenant order,
    drawn from ``seed`` at a 1/sqrt(fan-in) scale."""
    rng = np.random.default_rng(seed)
    a, b, p = geom["alpha"], geom["beta"], geom["p"]
    fan_in = a * p * p
    return [
        rng.standard_normal((a, b, p, p)).astype(np.float32) / np.sqrt(fan_in)
        for _ in range(tenants)
    ]


def _patches(images: np.ndarray, geom: dict) -> np.ndarray:
    """im2col: ``(N, alpha, m, m)`` -> ``(N, n, n, alpha * p * p)``."""
    p, s, pad = geom["p"], geom["stride"], geom["pad"]
    x = np.pad(images, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
    n = (images.shape[-1] + 2 * pad - p) // s + 1
    cols = [
        x[:, :, i : i + s * n : s, j : j + s * n : s]
        for i in range(p) for j in range(p)
    ]                                           # each (N, alpha, n, n)
    # -> (N, n, n, alpha, p*p), flattened alpha-major to match the kernels.
    return np.stack(cols, axis=-1).transpose(0, 2, 3, 1, 4).reshape(
        images.shape[0], n, n, -1
    )


def conv(images: np.ndarray, kernels: np.ndarray, geom: dict) -> np.ndarray:
    """``(N, alpha, m, m)`` * ``(alpha, beta, p, p)`` -> ``(N, beta, n, n)``,
    a cross-correlation with the configuration's stride and padding, in
    float64."""
    cols = _patches(np.asarray(images, np.float64), geom)
    alpha, beta = kernels.shape[:2]
    w = np.asarray(kernels, np.float64).reshape(alpha, beta, -1)
    w = w.transpose(0, 2, 1).reshape(-1, beta)        # (alpha*p*p, beta)
    return (cols @ w).transpose(0, 3, 1, 2)


def bf16(a: np.ndarray) -> np.ndarray:
    """Round float32 values to the nearest bfloat16 (ties to even), kept as
    float32."""
    bits = np.ascontiguousarray(a, np.float32).view(np.uint32)
    bits = (bits + np.uint32(0x7FFF) + ((bits >> 16) & np.uint32(1))) & np.uint32(
        0xFFFF0000
    )
    return bits.view(np.float32)


def conv_high(images: np.ndarray, kernels: np.ndarray, geom: dict) -> np.ndarray:
    """The control: :func:`conv` as three bfloat16 passes, ``hi*hi + hi*lo +
    lo*hi`` -- what a float32 product at ``high`` precision computes."""
    x = np.asarray(images, np.float32)
    k = np.asarray(kernels, np.float32)
    xh, kh = bf16(x), bf16(k)
    xl, kl = bf16(x - xh), bf16(k - kh)
    return conv(xh, kh, geom) + conv(xh, kl, geom) + conv(xl, kh, geom)


def unpermuted(perms: dict[int, np.ndarray], beta: int) -> int:
    """How many tenants' recorded permutations are not a permutation of the
    ``beta`` channels, or leave every channel in place."""
    ident = np.arange(beta)
    return sum(
        1 for p in perms.values()
        if np.asarray(p).shape != (beta,)
        or not np.array_equal(np.sort(p), ident)
        or np.array_equal(p, ident)
    )


def compare(sample: dict, geom: dict, kernels: list[np.ndarray],
            perms: dict[int, np.ndarray], produce=None) -> dict:
    """Hold every sampled request to the reference.

    ``sample`` holds ``tenant`` (R,), ``images`` (R, b, alpha, m, m) and
    ``served`` (R, b, beta, n, n); ``perms`` each tenant's channel
    permutation.  ``produce(images, kernels)`` stands in for what was served
    when given (the control), in the reference's channel order.  Returns the
    largest ``|served - reference|``, the images compared and the distinct
    tenants they came from.
    """
    worst, images = 0.0, 0
    tenants = np.asarray(sample["tenant"])
    for r, t in enumerate(tenants):
        x = sample["images"][r]
        want = conv(x, kernels[t], geom)
        if produce is None:
            got = np.asarray(sample["served"][r], np.float64)
            want = want[:, np.asarray(perms[int(t)])]
        else:
            got = np.asarray(produce(x, kernels[t], geom), np.float64)
        if got.shape != want.shape:
            return {"max_abs_err": float("inf"), "compared_images": images,
                    "compared_tenants": int(np.unique(tenants[:r]).size)}
        worst = max(worst, float(np.abs(got - want).max()))
        images += x.shape[0]
    return {"max_abs_err": worst, "compared_images": images,
            "compared_tenants": int(np.unique(tenants).size)}
