"""Slot-indexed grouped kernels (kernels.grouped / the grouped ops entry
points): exact equivalence against the materialized-gather oracle for every
index-vector shape the delivery engine can produce — identity, partial table
(T < capacity), out-of-order, duplicate slots — on both backend legs (jnp
reference and Pallas interpret), plus row padding and the refusal of
whole-axis blocks for widths off the 128-lane tile on the Pallas backends,
the padding-index clamp, and padding groups past the live count, which
read no slot."""
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
from repro.kernels.grouped import tiled_group_gemm

BACKENDS = ("jnp", "interpret")

# Index vectors over a 6-slot table, 4 groups: every engine-reachable shape.
GIDX_CASES = {
    "identity": [0, 1, 2, 3],
    "partial_table": [0, 1, 2, 4],       # T < capacity, in slot order
    "out_of_order": [4, 0, 5, 2],
    "duplicates": [3, 3, 1, 3],          # one tenant overflowing max_rows
}


def _token_morph_oracle(tokens, perms):
    """Materialized-gather twin: each group gathers through its own (V,)
    permutation row -> (G, B, L)."""
    return jax.vmap(lambda p, t: p[t])(perms, tokens)


def _aug_embed_oracle(tokens, tables):
    """Materialized-gather twin: each group gathers from its own (V, d)
    table -> (G, B, L, d)."""
    return jax.vmap(lambda e, t: e[t])(tables, tokens)


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


def _rows_tiled(t, gidx, w, bm, bn, bk):
    """The rows lane's layout on the tiled kernel: ``t`` (G, R, K), each
    group ``R // bm`` whole row tiles, read as (G * R, K) and multiplied by
    its slot's matrix."""
    G, R, K = t.shape
    per = R // bm
    out = tiled_group_gemm(
        t.reshape(G * R, K), jnp.repeat(gidx, per),
        jnp.array([G * per], jnp.int32), w, bm=bm, bn=bn, bk=bk,
        interpret=True, name="mole_test_rows",
    )
    return out.reshape(G, R, -1)


@pytest.mark.parametrize("name", sorted(GIDX_CASES))
def test_grouped_pallas_kernels_match_ref_directly(rng, name):
    """The raw Pallas kernel (scalar-prefetched index maps, interpret mode)
    in the rows lane's layout, several row, contraction and output blocks
    a group, against the scan reference — no dispatch layer in between."""
    gidx_np = np.array(GIDX_CASES[name], np.int32)
    G, S = len(gidx_np), 6
    gidx = jnp.asarray(gidx_np)

    # The morph: rows cut to the core width, (G, B * kappa, q) @ cores.
    B, kappa, q = 16, 2, 128
    x = jnp.asarray(rng.standard_normal((G, B, kappa * q)).astype(np.float32))
    cores = jnp.asarray(
        (rng.standard_normal((S, q, q)) / np.sqrt(q)).astype(np.float32)
    )
    got = _rows_tiled(x.reshape(G, B * kappa, q), gidx, cores,
                      bm=8, bn=64, bk=64).reshape(G, B, kappa * q)
    want = ref.block_diag_matmul_grouped_ref(x, gidx, cores, kappa)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)

    K, N = 256, 128
    t = jnp.asarray(rng.standard_normal((G, B, K)).astype(np.float32))
    c_acs = jnp.asarray(
        (rng.standard_normal((S, K, N)) / 16).astype(np.float32)
    )
    got = _rows_tiled(t, gidx, c_acs, bm=8, bn=64, bk=128)
    want = ref.aug_gemm_grouped_ref(t, gidx, c_acs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


@pytest.mark.parametrize("backend", BACKENDS)
def test_padding_groups_read_no_slot(rng, backend):
    """Groups past ``n_real`` are padding: their output is zeros and their
    slots are not multiplied.  The slots only the padding groups name hold
    NaN, and the padding groups' rows are not even zero: the result is
    finite, the live groups equal the gather oracle, the padding zero."""
    G, B, kappa, q, K, N, S, n_real = 4, 5, 2, 128, 256, 128, 6, 2
    gidx = jnp.asarray(np.array([1, 3, 5, 0], np.int32))  # pads: 5, 0
    cores = rng.standard_normal((S, q, q)).astype(np.float32) / np.sqrt(q)
    c_acs = rng.standard_normal((S, K, N)).astype(np.float32) / 16
    cores[[5, 0]] = np.nan
    c_acs[[5, 0]] = np.nan
    cores, c_acs = jnp.asarray(cores), jnp.asarray(c_acs)
    x = jnp.asarray(rng.standard_normal((G, B, kappa * q)).astype(np.float32))
    t = jnp.asarray(rng.standard_normal((G, B, K)).astype(np.float32))
    live = jnp.array([n_real], jnp.int32)
    cases = [
        (morph_rows_grouped(x, gidx, cores, kappa, backend=backend,
                            n_real=live),
         ref.block_diag_matmul_batched_ref(x[:n_real], cores[gidx[:n_real]],
                                           kappa)),
        (aug_conv_forward_grouped(t, gidx, c_acs, backend=backend,
                                  n_real=live),
         ref.aug_gemm_batched_ref(t[:n_real], c_acs[gidx[:n_real]])),
    ]
    for got, want in cases:
        got = np.asarray(got)
        assert np.all(np.isfinite(got))
        np.testing.assert_allclose(got[:n_real], np.asarray(want), atol=1e-4)
        np.testing.assert_array_equal(got[n_real:], 0.0)


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
        np.asarray(_token_morph_oracle(tokens, perms[gidx])),
    )
    np.testing.assert_allclose(
        np.asarray(aug_embed_grouped(tokens, gidx, tables, backend=backend)),
        np.asarray(_aug_embed_oracle(tokens, tables[gidx])),
        atol=0,
    )


@pytest.mark.parametrize("groups", [[2, 2, 0, 2, 5, 0, 2, 2, 2], [1] * 9],
                         ids=["uneven", "one_group"])
def test_tiled_group_gemm_serves_rows_grouped_into_padded_tiles(rng, groups):
    """The ragged kernel behind the dropless experts and the grouped
    Aug-head: rows sorted by group into whole 8-row tiles, each tile times
    its group's matrix, tiles past the used count zero, every row equal to
    its own group's product."""
    from repro.kernels.ops import group_tiles, tile_count

    g = jnp.asarray(groups, jnp.int32)
    x = jnp.asarray(rng.standard_normal((g.shape[0], 256)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((6, 256, 384)), jnp.float32)
    bm = 8
    tiles = group_tiles(g, 6, bm)
    n = tile_count(g.shape[0], 6, bm)
    buf = jnp.zeros((n * bm, 256), jnp.float32).at[tiles["slot"]].set(
        x[tiles["order"]])
    out = tiled_group_gemm(buf, tiles["tile_group"], tiles["n_used"], w,
                           bm=bm, bn=128, bk=128, interpret=True,
                           name="mole_test_gmm")
    used = int(tiles["n_used"][0])
    counts = np.bincount(groups)
    assert used == int(np.sum(-(-counts // bm))) and used <= n
    np.testing.assert_array_equal(np.asarray(out[used * bm:]), 0.0)
    got = np.asarray(out)[np.asarray(tiles["slot"])]
    want = np.einsum("rk,rkn->rn", np.asarray(x)[np.asarray(tiles["order"])],
                     np.asarray(w)[np.asarray(g)[np.asarray(tiles["order"])]])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
