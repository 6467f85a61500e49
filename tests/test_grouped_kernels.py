"""Slot-indexed grouped kernels (kernels.grouped / the grouped ops entry
points): exact equivalence against the materialized-gather oracle for every
index-vector shape the delivery engine can produce — identity, partial table
(T < capacity), out-of-order, duplicate slots — on both backend legs (jnp
reference and Pallas interpret), plus row padding and the refusal of
whole-axis blocks for widths off the 128-lane tile on the Pallas backends,
and the padding-index clamp."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (
    aug_conv_forward_grouped,
    aug_embed_grouped,
    morph_rows_grouped,
    ref,
    token_morph_grouped,
)
from repro.kernels.grouped import grouped_aug_gemm

BACKENDS = ("jnp", "interpret")

# Index vectors over a 6-slot table, 4 groups: every engine-reachable shape.
GIDX_CASES = {
    "identity": [0, 1, 2, 3],
    "partial_table": [0, 1, 2, 4],       # T < capacity, in slot order
    "out_of_order": [4, 0, 5, 2],
    "duplicates": [3, 3, 1, 3],          # one tenant overflowing max_rows
}


def _case_id(kv):
    return kv if isinstance(kv, str) else None


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("name", sorted(GIDX_CASES))
def test_morph_rows_grouped_matches_gather_oracle(rng, backend, name):
    """Tileable shapes: grouped morph == morph with materialized cores[gidx]."""
    G, B, kappa, q, S = 4, 8, 2, 128, 6
    x = jnp.asarray(rng.standard_normal((G, B, kappa * q)).astype(np.float32))
    cores = jnp.asarray(
        (rng.standard_normal((S, q, q)) / np.sqrt(q)).astype(np.float32)
    )
    gidx = jnp.asarray(np.array(GIDX_CASES[name], np.int32))
    got = morph_rows_grouped(x, gidx, cores, kappa, backend=backend)
    want = ref.block_diag_matmul_batched_ref(x, cores[gidx], kappa)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("name", sorted(GIDX_CASES))
def test_aug_conv_grouped_matches_gather_oracle(rng, backend, name):
    """Tileable shapes: grouped Aug-Conv == GEMM with materialized c_acs[gidx]."""
    G, B, K, N, S = 4, 8, 256, 128, 6
    t = jnp.asarray(rng.standard_normal((G, B, K)).astype(np.float32))
    c_acs = jnp.asarray(
        (rng.standard_normal((S, K, N)) / 16).astype(np.float32)
    )
    gidx = jnp.asarray(np.array(GIDX_CASES[name], np.int32))
    got = aug_conv_forward_grouped(t, gidx, c_acs, backend=backend)
    want = ref.aug_gemm_batched_ref(t, c_acs[gidx])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


@pytest.mark.parametrize("backend", BACKENDS)
def test_grouped_untileable_shapes_fall_back_to_ref(rng, backend):
    """Shapes off the kernels' tiles.  The jnp backend runs the scan
    reference for any shape.  The Pallas backends never fall back to it:
    B = 5 rows are zero-padded to the 8-row tile (and sliced off the
    result), and a 10-wide core repeated kappa = 3 times, K = 600 and N = 9
    run as whole-axis blocks."""
    G, B, kappa, q, S = 3, 5, 3, 10, 4
    x = jnp.asarray(rng.standard_normal((G, B, kappa * q)).astype(np.float32))
    cores = jnp.asarray(rng.standard_normal((S, q, q)).astype(np.float32))
    gidx = jnp.asarray(np.array([2, 0, 2], np.int32))
    # Aug: K = 600 is no multiple of the 128-lane tile, N = 9 is narrower.
    t = jnp.asarray(rng.standard_normal((G, B, 600)).astype(np.float32))
    c = jnp.asarray((rng.standard_normal((S, 600, 9)) / 24).astype(np.float32))
    cases = [
        (lambda: morph_rows_grouped(x, gidx, cores, kappa, backend=backend),
         ref.block_diag_matmul_batched_ref(x, cores[gidx], kappa)),
        (lambda: aug_conv_forward_grouped(t, gidx, c, backend=backend),
         ref.aug_gemm_batched_ref(t, c[gidx])),
    ]
    for run, want in cases:
        jaxpr = str(jax.make_jaxpr(run)())
        assert ("pallas_call" in jaxpr) == (backend != "jnp")
        got = run()
        assert got.shape == want.shape
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-4
        )


@pytest.mark.parametrize("backend", BACKENDS)
def test_grouped_clamps_out_of_range_padding_index(rng, backend):
    """A padding group's slot index past the table must not fault: the entry
    points clamp it, and the padding rows are zero so the result is zero."""
    G, B, kappa, q, S = 2, 8, 1, 128, 2
    x = np.zeros((G, B, kappa * q), np.float32)
    x[0] = rng.standard_normal((B, kappa * q)).astype(np.float32)
    cores = jnp.asarray(
        (rng.standard_normal((S, q, q)) / np.sqrt(q)).astype(np.float32)
    )
    gidx = jnp.asarray(np.array([1, S + 3], np.int32))  # second group: padding
    got = np.asarray(
        morph_rows_grouped(jnp.asarray(x), gidx, cores, kappa, backend=backend)
    )
    want = np.asarray(ref.block_diag_matmul_ref(jnp.asarray(x[0]), cores[1], kappa))
    np.testing.assert_allclose(got[0], want, atol=1e-4)
    assert np.all(got[1] == 0.0)


@pytest.mark.parametrize("name", sorted(GIDX_CASES))
def test_grouped_pallas_kernels_match_ref_directly(rng, name):
    """The raw Pallas kernels (scalar-prefetched index maps, interpret mode)
    against the scan reference — no dispatch layer in between."""
    gidx_np = np.array(GIDX_CASES[name], np.int32)
    G, S = len(gidx_np), 6
    gidx = jnp.asarray(gidx_np)

    # The morph: rows cut to the core width, (G, B * kappa, q) @ cores.
    B, kappa, q = 16, 2, 128
    x = jnp.asarray(rng.standard_normal((G, B, kappa * q)).astype(np.float32))
    cores = jnp.asarray(
        (rng.standard_normal((S, q, q)) / np.sqrt(q)).astype(np.float32)
    )
    got = grouped_aug_gemm(
        x.reshape(G, B * kappa, q), gidx, cores, bm=8, bn=64, bk=64,
        interpret=True,
    ).reshape(G, B, kappa * q)
    want = ref.block_diag_matmul_grouped_ref(x, gidx, cores, kappa)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)

    K, N = 256, 128
    t = jnp.asarray(rng.standard_normal((G, B, K)).astype(np.float32))
    c_acs = jnp.asarray(
        (rng.standard_normal((S, K, N)) / 16).astype(np.float32)
    )
    got = grouped_aug_gemm(t, gidx, c_acs, bm=8, bn=64, bk=128, interpret=True)
    want = ref.aug_gemm_grouped_ref(t, gidx, c_acs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("name", sorted(GIDX_CASES))
def test_token_lanes_grouped_match_gather_oracle(rng, backend, name):
    """LM lanes: grouped token morph / Aug-Embedding == their materialized-
    gather twins (integer results, so equality is exact)."""
    G, B, L, V, d, S = 4, 3, 9, 101, 8, 6
    tokens = jnp.asarray(rng.integers(0, V, (G, B, L)).astype(np.int32))
    perms = jnp.asarray(
        np.stack([rng.permutation(V) for _ in range(S)]).astype(np.int32)
    )
    tables = jnp.asarray(rng.standard_normal((S, V, d)).astype(np.float32))
    gidx = jnp.asarray(np.array(GIDX_CASES[name], np.int32))
    np.testing.assert_array_equal(
        np.asarray(token_morph_grouped(tokens, gidx, perms, backend=backend)),
        np.asarray(ref.token_morph_batched_ref(tokens, perms[gidx])),
    )
    np.testing.assert_allclose(
        np.asarray(aug_embed_grouped(tokens, gidx, tables, backend=backend)),
        np.asarray(ref.aug_embed_batched_ref(tokens, tables[gidx])),
        atol=0,
    )
