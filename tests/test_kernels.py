"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracles."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import aug_conv_forward, morph_rows, ref
from repro.kernels.aug_gemm import aug_gemm


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4), (jnp.bfloat16, 2e-1)])
@pytest.mark.parametrize("R,kappa,q", [
    (128, 1, 128), (128, 3, 128), (8, 4, 128), (256, 2, 256), (64, 6, 128),
])
def test_block_diag_sweep(rng, R, kappa, q, dtype, tol):
    """The repeated-block-diagonal morph through the Pallas GEMM kernel."""
    x = jnp.asarray(rng.standard_normal((R, kappa * q)), dtype)
    core = jnp.asarray(rng.standard_normal((q, q)) / np.sqrt(q), dtype)
    got = morph_rows(x, core, kappa, backend="interpret")
    want = ref.block_diag_matmul_ref(x, core, kappa)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol
    )


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4), (jnp.bfloat16, 2e-1)])
@pytest.mark.parametrize("B,K,N", [(128, 512, 128), (8, 1024, 256), (64, 512, 384)])
def test_aug_gemm_sweep(rng, B, K, N, dtype, tol):
    t = jnp.asarray(rng.standard_normal((B, K)), dtype)
    c = jnp.asarray(rng.standard_normal((K, N)) / np.sqrt(K), dtype)
    got = aug_gemm(t, c, bm=min(128, B), bn=128, bk=512, interpret=True)
    want = ref.aug_gemm_ref(t, c)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol
    )


@settings(max_examples=15, deadline=None)
@given(
    r_blocks=st.integers(1, 3), kappa=st.integers(1, 4),
    q_mult=st.sampled_from([128, 256]), seed=st.integers(0, 2**31 - 1),
)
def test_block_diag_property(r_blocks, kappa, q_mult, seed):
    g = np.random.default_rng(seed)
    R, q = 128 * r_blocks, q_mult
    x = jnp.asarray(g.standard_normal((R, kappa * q)).astype(np.float32))
    core = jnp.asarray((g.standard_normal((q, q)) / np.sqrt(q)).astype(np.float32))
    got = morph_rows(x, core, kappa, backend="interpret")
    want = ref.block_diag_matmul_ref(x, core, kappa)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_public_wrappers_fallback(rng):
    """Off-tile shapes: jnp computes the reference.  Interpret (like
    compiled Pallas) never falls back to it: a 10-wide core repeated 3
    times, and 7 rows against K = 33 / N = 9, run the kernel with
    whole-axis blocks and rows padded to the 8-row tile."""
    x = jnp.asarray(rng.standard_normal((10, 30)).astype(np.float32))
    core = jnp.asarray(rng.standard_normal((10, 10)).astype(np.float32))
    t = jnp.asarray(rng.standard_normal((7, 33)).astype(np.float32))
    c = jnp.asarray(rng.standard_normal((33, 9)).astype(np.float32))
    cases = [
        (partial(morph_rows, kappa=3), (x, core),
         ref.block_diag_matmul_ref(x, core, 3)),
        (aug_conv_forward, (t, c), ref.aug_gemm_ref(t, c)),
    ]
    for fn, args, want in cases:
        for be in ("jnp", "interpret"):
            f = partial(fn, backend=be)
            jaxpr = str(jax.make_jaxpr(f)(*args))
            assert ("pallas_call" in jaxpr) == (be == "interpret"), be
            got = f(*args)
            assert got.shape == want.shape
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), atol=1e-4
            )


@pytest.mark.parametrize("T,D,chunk", [(64, 16, 16), (128, 32, 32), (96, 64, 32)])
def test_wkv6_kernel_sweep(rng, T, D, chunk):
    """Pallas wkv6 scan (interpret) vs the naive-recurrence oracle."""
    from repro.kernels.wkv6 import wkv6_chunked

    B, H = 2, 2
    r, k, v = [
        jnp.asarray(rng.standard_normal((B, H, T, D)).astype(np.float32))
        for _ in range(3)
    ]
    logw = -jnp.exp(jnp.asarray(rng.standard_normal((B, H, T, D)).astype(np.float32)))
    u = jnp.asarray(rng.standard_normal((H, D)).astype(np.float32))
    s0 = jnp.asarray(rng.standard_normal((B, H, D, D)).astype(np.float32)) * 0.1
    ref_out, ref_s = ref.wkv6_ref(r, k, v, logw, u, s0)
    BH = B * H
    flat = lambda x: x.reshape(BH, *x.shape[2:])
    u_b = jnp.broadcast_to(u[None], (B, H, D)).reshape(BH, D)
    out, sf = wkv6_chunked(flat(r), flat(k), flat(v), flat(logw), u_b, flat(s0),
                           chunk=chunk, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out.reshape(B, H, T, D)), np.asarray(ref_out), atol=2e-3
    )
    np.testing.assert_allclose(
        np.asarray(sf.reshape(B, H, D, D)), np.asarray(ref_s), atol=2e-3
    )


def test_wkv6_model_path_matches_kernel(rng):
    """models/blocks._wkv_chunked (XLA path, incl. subchunked form) agrees
    with the Pallas kernel on the same inputs."""
    from repro.kernels.wkv6 import wkv6_chunked
    from repro.models.blocks import _wkv_chunked

    B, H, T, D = 1, 2, 128, 16
    r, k, v = [
        jnp.asarray(rng.standard_normal((B, H, T, D)).astype(np.float32))
        for _ in range(3)
    ]
    logw = -jnp.exp(jnp.asarray(rng.standard_normal((B, H, T, D)).astype(np.float32)))
    u = jnp.asarray(rng.standard_normal((H, D)).astype(np.float32))
    s0 = jnp.zeros((B, H, D, D), jnp.float32)
    out_x, s_x = _wkv_chunked(r, k, v, logw, u, s0, chunk=64, subchunk=16)
    u_b = jnp.broadcast_to(u[None], (B, H, D)).reshape(B * H, D)
    fl = lambda x: x.reshape(B * H, *x.shape[2:])
    out_k, s_k = wkv6_chunked(fl(r), fl(k), fl(v), fl(logw), u_b, fl(s0),
                              chunk=32, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out_x), np.asarray(out_k.reshape(B, H, T, D)), atol=2e-3
    )
    np.testing.assert_allclose(
        np.asarray(s_x), np.asarray(s_k.reshape(B, H, D, D)), atol=2e-3
    )


def test_kernel_equals_protocol_math(rng):
    """morph via kernel == protocol-level morphing (same M semantics)."""
    from repro.core import make_core, morph
    core = make_core(rng, 512, kappa=4)
    x = jnp.asarray(rng.standard_normal((128, 512)).astype(np.float32))
    via_kernel = morph_rows(x, jnp.asarray(core.matrix), 4)
    via_core = morph(x, core)
    np.testing.assert_allclose(
        np.asarray(via_kernel), np.asarray(via_core), atol=1e-4
    )
