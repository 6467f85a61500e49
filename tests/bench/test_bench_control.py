"""The control of the check fails it: the float64 reference in the precision
below the configuration's (three bfloat16 passes, ``high``) reads above each
configuration's limit, at the cell's geometry, on a sample of a few images."""
import dataclasses

import numpy as np
import pytest
from jax import lax

import bench_tiny  # noqa: F401
from bench import control, reference
from bench.harness import ROOT, load_cell


@pytest.mark.parametrize("workload", ["vgg16_cifar.infer", "resnet20_cifar.train"])
def test_control_reads_above_the_limit(workload):
    cell = load_cell(ROOT, workload)
    small = dataclasses.replace(
        cell, traffic=dict(cell.traffic, check_per_tenant=1,
                           images_per_request=min(8, cell.traffic["images_per_request"])))
    r = control.readings(small, 2 ** 31 + 11)
    assert r["high"] > r["limit"]
    assert r["bf16"] > r["high"]


def test_reference_matches_a_plain_convolution():
    g = {"alpha": 3, "m": 8, "p": 3, "stride": 1, "pad": 1, "beta": 5}
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 3, 8, 8)).astype(np.float32)
    k = rng.standard_normal((3, 5, 3, 3)).astype(np.float32)
    want = lax.conv_general_dilated(
        x, k.transpose(1, 0, 2, 3), (1, 1), [(1, 1)] * 2,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=lax.Precision.HIGHEST,
    )
    np.testing.assert_allclose(reference.conv(x, k, g), np.asarray(want),
                               atol=1e-5)


def test_bf16_rounds_to_nearest_even():
    x = np.array([1.0, 1 + 2 ** -8, 1 + 3 * 2 ** -8, -2.5, 1 + 2 ** -9],
                 np.float32)
    np.testing.assert_array_equal(
        reference.bf16(x), [1.0, 1.0, 1 + 2 ** -6, -2.5, 1.0])


def test_compare_holds_each_tenant_to_its_recorded_permutation():
    g = {"alpha": 2, "m": 6, "p": 3, "stride": 1, "pad": 1, "beta": 4}
    rng = np.random.default_rng(1)
    k = [rng.standard_normal((2, 4, 3, 3)).astype(np.float32) for _ in range(2)]
    x = rng.standard_normal((3, 1, 2, 6, 6)).astype(np.float32)
    tenant = np.array([0, 1, 1])
    perms = {0: np.array([2, 0, 3, 1]), 1: np.array([1, 3, 0, 2])}
    served = np.stack([reference.conv(xi, k[t], g)[:, perms[t]]
                       for xi, t in zip(x, tenant)])
    sample = {"tenant": tenant, "images": x, "served": served.astype(np.float32)}
    ok = reference.compare(sample, g, k, perms)
    assert ok["max_abs_err"] < 1e-6
    assert ok["compared_images"] == 3 and ok["compared_tenants"] == 2
    # Served in the plain channel order, or in another tenant's order.
    plain = np.stack([reference.conv(xi, k[t], g) for xi, t in zip(x, tenant)])
    assert reference.compare(dict(sample, served=plain), g, k, perms)[
        "max_abs_err"] > 0.1
    assert reference.compare(sample, g, k, {0: perms[1], 1: perms[0]})[
        "max_abs_err"] > 0.1
    moved = served.copy()
    moved[2] = moved[2][:, [1, 0, 2, 3]]              # one request's order moves
    assert reference.compare(dict(sample, served=moved), g, k, perms)[
        "max_abs_err"] > 0.1


def test_unpermuted_counts_identities_and_non_permutations():
    assert reference.unpermuted({0: np.array([1, 0, 2])}, 3) == 0
    assert reference.unpermuted({0: np.arange(3), 1: np.array([0, 0, 1]),
                                 2: np.array([1, 0])}, 3) == 3
