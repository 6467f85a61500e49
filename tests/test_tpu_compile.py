"""Compile the served delivery step for a TPU v5e that is described, not
attached: the paper's VGG-16/CIFAR first layer (alpha=3, m=32, beta=64,
p=3 -> a 3072 x 65536 Aug-Conv matrix per slot) through the grouped Pallas
kernels, the decode head at deepseek-7B's width, and DeepSeek-V2-Lite's
dropless routed experts at a 128-row decode step.  The TPU compiler
refuses here what interpret mode accepts: a block off the (8, 128) tiling,
more VMEM than a kernel may use, a program larger than the chip.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler library, and every test
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import ConvGeometry
from repro.kernels.ops import _expert_ffn, _lm_head_rows_grouped
from repro.runtime.engine import _delivery_step

PAPER = ConvGeometry(alpha=3, beta=64, m=32, p=3)
SLOTS, GROUPS = 4, 4
HBM_BYTES = 16 * 10**9   # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _custom_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def _compile_step(sharding, geom, kappa, rows):
    f_in, f_out = geom.in_features, geom.out_features
    q = f_in // kappa
    f32 = jnp.float32
    return _delivery_step.lower(
        _spec((GROUPS, rows, f_in), f32, sharding),
        _spec((GROUPS,), jnp.int32, sharding),
        _spec((1,), jnp.int32, sharding),
        _spec((SLOTS, q, q), f32, sharding),
        _spec((SLOTS, f_in, f_out), f32, sharding),
        kappa=kappa, backend="pallas",
    ).compile()


@pytest.mark.parametrize("rows", [1, 8])
@pytest.mark.parametrize("kappa", [1, 3])
def test_delivery_step_compiles_at_paper_geometry(one_chip, kappa, rows):
    """Both grouped kernels (morph + Aug-Conv) compile as Mosaic custom
    calls; B = 1 proves the rows are padded to the 8-row tile rather than
    routed to the XLA reference."""
    compiled = _compile_step(one_chip, PAPER, kappa, rows)
    assert _custom_calls(compiled) == 2
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= (
        SLOTS * PAPER.in_features * PAPER.out_features * 4
    )
    assert mem.argument_size_in_bytes < HBM_BYTES


@pytest.mark.parametrize("geom,kappa", [
    # The benchmark's served chaos point: 72 features, a 36-wide core.
    (ConvGeometry(alpha=2, beta=4, m=6, p=3), 2),
    # serve.py --kappa 4 at its default geometry: a 192-wide core.
    (ConvGeometry(alpha=3, beta=16, m=16, p=3), 4),
], ids=["f72-k2", "f768-k4"])
def test_delivery_step_compiles_at_narrow_cores(one_chip, geom, kappa):
    """A core off the 128-lane grid is one whole-axis block, which Mosaic
    accepts: both kernels still compile, 3 rows padded to 8."""
    assert _custom_calls(_compile_step(one_chip, geom, kappa, 3)) == 2


def test_grouped_kernels_keep_their_names(one_chip):
    """The morph and the Aug-Conv product run one kernel body; each call
    names its kernel, and the compiled step's instructions (the operations
    a device trace shows) carry those names, not the jitted wrappers'."""
    text = _compile_step(one_chip, PAPER, 1, 8).as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 2
    assert any("%mole_morph_grouped" in ln for ln in calls)
    assert any("%mole_aug_conv_grouped" in ln for ln in calls)


def test_lm_head_compiles_at_decode_width(one_chip):
    """The batched-decode Aug-head GEMM at d=4096, V=102400 in bf16."""
    d, vocab, rows = 4096, 102400, 8
    bf16 = jnp.bfloat16
    compiled = _lm_head_rows_grouped.lower(
        _spec((rows, d), bf16, one_chip),
        _spec((rows,), jnp.int32, one_chip),
        _spec((SLOTS, d, vocab), bf16, one_chip),
        backend="pallas",
    ).compile()
    assert _custom_calls(compiled) == 1
    assert compiled.memory_analysis().argument_size_in_bytes < HBM_BYTES


def test_expert_ffn_compiles_at_deepseek_v2_lite_widths(one_chip):
    """The dropless routed experts of a 128-row decode step: 64 experts of
    width 1,408 at d=2,048, top-6, in bf16 -- three grouped GEMMs, each the
    kernel named ``mole_expert_gmm``."""
    rows, k, d, f, experts = 128, 6, 2048, 1408, 64
    bf16 = jnp.bfloat16
    compiled = _expert_ffn.lower(
        _spec((rows, d), bf16, one_chip),
        _spec((rows, k), jnp.int32, one_chip),
        _spec((rows, k), jnp.float32, one_chip),
        _spec((experts, d, f), bf16, one_chip),
        _spec((experts, d, f), bf16, one_chip),
        _spec((experts, f, d), bf16, one_chip),
        act="swiglu", backend="pallas",
    ).compile()
    calls = [ln for ln in compiled.as_text().splitlines()
             if "tpu_custom_call" in ln]
    assert len(calls) == 3
    assert all("%mole_expert_gmm" in ln for ln in calls)
