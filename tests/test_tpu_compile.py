"""Compile every Pallas kernel for a described TPU v5e (no chip needed).

Interpret mode on the CPU cannot show what the TPU's compiler refuses:
block shapes off the (8, 128) tiling, or more VMEM than a kernel may
use. These tests lower each kernel with Mosaic at the trainer's K-FAC
block (128) and at the largest block the CLI allows (1024), for one
chip of a v5e:2x2 topology that is described, not attached.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and each test worker
imports every test file.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.quantize import split_hi_lo_bf16
from repro.kernels.bitslice_mm import bitslice_mm
from repro.kernels.fused_gram_solve import fused_gram_inv
from repro.kernels.fused_precond import fused_precond
from repro.kernels.neumann_inv import neumann_inv
from repro.kernels.smw_update import smw_update


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU executable written to the persistent cache cannot be read
    # back without a chip: keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _cases(bs):
    """(kernel, argument shapes) at SOI block width ``bs``."""
    return {
        "fused_precond": (fused_precond,
                          [(16, bs, bs), (16, bs, bs), (16, bs, bs)]),
        "neumann_inv": (neumann_inv, [(16, bs, bs), (16,)]),
        "fused_gram_inv": (fused_gram_inv, [(2048, 4, bs)]),
        "smw_update": (lambda inv, v: smw_update(inv, v, decay=0.95,
                                                 cscale=0.05),
                       [(16, bs, bs), (16, 64, bs)]),
    }


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    # Mosaic lowered the kernel: no interpreter, no XLA fallback
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("bs", [128, 1024])
@pytest.mark.parametrize("kernel", ["fused_precond", "neumann_inv",
                                    "fused_gram_inv", "smw_update"])
def test_block_kernel_compiles_for_v5e(one_chip, kernel, bs):
    fn, shapes = _cases(bs)[kernel]
    _compile(fn, shapes, one_chip)


def test_bitslice_mm_compiles_for_v5e(one_chip):
    """qwen2-0.5b's MLP up-projection: 2048 tokens x 896 @ 896 x 4864."""
    _compile(bitslice_mm, [(2048, 896), (896, 4864)], one_chip)


def test_hilo_split_keeps_its_lo_slice_for_v5e(one_chip):
    """XLA for the TPU folds an ``f32 -> bf16 -> f32`` convert pair to the
    identity, which zeroed every lo slice on the chip (8-bit inverses).
    The split rounds on the bits, which the compiled program must keep."""
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32, sharding=one_chip)
    text = jax.jit(split_hi_lo_bf16).lower(x).compile().as_text()
    assert "shift-right-logical" in text
    assert "subtract" in text


@pytest.mark.parametrize("kernel", ["fused_precond", "neumann_inv",
                                    "fused_gram_inv", "smw_update",
                                    "bitslice_mm"])
def test_kernel_splits_on_the_bits(kernel):
    """Each kernel body takes its hi/lo slices from the same bit-rounding
    split as the XLA path (Mosaic lowers no ``reduce_precision``), not
    from a convert pair a compiler may fold."""
    cases = {**_cases(128),
             "bitslice_mm": (bitslice_mm, [(256, 256), (256, 256)])}
    fn, shapes = cases[kernel]
    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    assert "shift_right_logical" in str(jax.make_jaxpr(fn)(*args))
