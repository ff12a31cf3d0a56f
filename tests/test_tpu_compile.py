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

import dataclasses
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get_smoke_config
from repro.core.kfac import KFACConfig
from repro.core.quantize import split_hi_lo_bf16
from repro.kernels.bitslice_mm import bitslice_mm
from repro.kernels.fused_gram_solve import fused_gram_inv
from repro.kernels.fused_precond import fused_precond
from repro.kernels.neumann_inv import neumann_inv
from repro.kernels.smw_update import smw_update
from repro.launch import steps as steps_mod
from repro.launch.train import KFACProgram


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


@pytest.mark.parametrize("hd", [64, 128])
def test_flash_attention_compiles_for_v5e(one_chip, hd):
    """The flash kernel's forward, dq and dkv calls at 1024 tokens (one
    1024 block), GQA 4 on 2, for head dim 64 and for 128, whose scale
    is no power of two."""
    from repro.kernels.flash_attention import causal_flash_attention

    def loss(q, k, v):
        return jnp.sum(causal_flash_attention(q, k, v).astype(jnp.float32))

    args = [jax.ShapeDtypeStruct((2, 1024, h, hd), jnp.bfloat16,
                                 sharding=one_chip) for h in (4, 2, 2)]
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3


def test_flash_attention_compiles_for_v5e_at_moonlight(one_chip):
    """Moonlight's MLA attention: 16 heads, query/key width 192, value
    width 128, one row of 8,192 tokens (eight 1024 blocks a side)."""
    from repro.kernels.flash_attention import causal_flash_attention

    def loss(q, k, v):
        return jnp.sum(causal_flash_attention(q, k, v).astype(jnp.float32))

    args = [jax.ShapeDtypeStruct((1, 8192, 16, d), jnp.bfloat16,
                                 sharding=one_chip) for d in (192, 192, 128)]
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3


def test_grouped_matmuls_compile_for_v5e_at_moonlight(one_chip):
    """The expert layer's grouped matmuls (forward and megablox's
    backward) and its per-expert Grams at Moonlight's widths: one 8k row
    of 6 choices over 8 held experts, 2,048 and 1,408 wide, and 128-wide
    K-FAC blocks."""
    from repro.kernels import grouped_matmul

    m, d, f, e = 8192 * 6, 2048, 1408, 8

    def loss(x, wg, wd, sizes):
        h = grouped_matmul.gmm(x, wg, sizes)
        y = grouped_matmul.gmm(h, wd, sizes)
        grams = grouped_matmul.group_gram(h, sizes, 128)
        return jnp.sum(y.astype(jnp.float32)) + jnp.sum(grams)

    args = [jax.ShapeDtypeStruct(s, t, sharding=one_chip) for s, t in (
        ((m, d), jnp.bfloat16), ((e, d, f), jnp.float32),
        ((e, f, d), jnp.float32), ((e + 1,), jnp.int32))]
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        *args).compile().as_text()
    # forward 2, gram 1, backward 2 x (gmm + tgmm)
    assert text.count('custom_call_target="tpu_custom_call"') == 7


def _custom_call_stacks(text):
    """The name stack (``op_name``) of each Mosaic custom call of a
    compiled program; an instruction's text runs to the next one."""
    stacks = []
    for m in re.finditer(r"\n  %\S+ = (.*?)(?=\n  %|\n\n|\n}\n)", text,
                         re.S):
        if 'custom_call_target="tpu_custom_call"' in m.group(1):
            name = re.search(r'op_name="([^"]*)"', m.group(1))
            stacks.append(name.group(1) if name else "")
    return stacks


def _under(scope, stack):
    return any(re.fullmatch(r"(?:\w+\()*%s\)*" % scope, c)
               for c in stack.split("/")[1:])


def test_flash_attention_is_charged_to_attn(one_chip):
    """The K-FAC train and statistics programs, at smoke widths with head
    dim 64 and 256 tokens, compiled for one v5e chip: attention lowers
    to the flash kernel's Mosaic calls, and each carries the ``attn``
    scope in its op metadata, on the forward, the recomputed forward and
    the backward, so the benchmark charges their time to attention."""
    cfg = dataclasses.replace(get_smoke_config("qwen2-0.5b"), head_dim=64,
                              soi_block=32)
    kcfg = KFACConfig(block_size=32, stats_every=2, inv_every=2,
                      stats_batch=2, stats_seq=256, inv_method="exact")
    prog = KFACProgram(cfg, kcfg)
    mesh = jax.sharding.Mesh(
        np.array(list(one_chip.device_set)).reshape(1, 1),
        ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)
    batch = {"tokens": jax.ShapeDtypeStruct((2, 256), jnp.int32)}
    with jax.set_mesh(mesh):
        prog.make_step(mesh)
        ab = steps_mod.abstract_train_state(cfg, kcfg)
        for name in ("train", "stats"):
            text = prog.programs[name].lower(ab, batch).compile().as_text()
            stacks = [s for s in _custom_call_stacks(text)
                      if "/pallas_call" in s and "flash_attention" in s]
            assert stacks, name
            assert all(_under("attn", s) for s in stacks), stacks
            passes = {"backward": [s for s in stacks
                                   if "/transpose(" in s
                                   and "/rematted_computation/" not in s],
                      "recomputed": [s for s in stacks
                                     if "/rematted_computation/" in s],
                      "forward": [s for s in stacks
                                  if "/transpose(" not in s]}
            for p, found in passes.items():
                assert found, (name, p, stacks)
