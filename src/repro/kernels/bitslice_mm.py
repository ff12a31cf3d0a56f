"""Pallas TPU kernel: bit-sliced (hi/lo bf16) matmul with fp32 S+A.

Paper mapping (RePAST Sec. II-B): a ReRAM VMM crossbar multiplies against
low-precision cells; high precision comes from splitting each operand
into bit slices and shift-adding the partial products in a digital S+A
unit. The TPU "cell" is the bf16 MXU operand; the hi/lo split
``x = x_hi + x_lo`` (each bf16) is the two-slice analogue, and the fp32
VMEM accumulator is the S+A unit. Partial products:

    a @ b = a_hi@b_hi + a_hi@b_lo + a_lo@b_hi   (+ a_lo@b_lo, dropped —
            below the fp32 noise floor, same argument as Eqn. 13
            dropping the A_1L*A_2L term)

Tiling: (bm, bk) x (bk, bn) MXU-aligned VMEM blocks; grid
(M/bm, N/bn, K/bk) with the K dimension innermost ("arbitrary") so each
output tile's accumulator lives in VMEM across the whole K sweep — the
slices never round-trip to HBM, exactly like the analog partial sums
never leave the crossbar's periphery.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantize import hilo_matmul

__all__ = ["bitslice_mm"]


def _kernel(a_ref, b_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # three bf16 MXU partial products, shift-added in the fp32 accumulator
    acc_ref[...] += hilo_matmul(a_ref[...], b_ref[...])

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


def _pad_dim(x: jax.Array, axis: int, mult: int) -> jax.Array:
    d = x.shape[axis]
    pad = (-d) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def bitslice_mm(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = 256,
    bn: int = 256,
    bk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """fp32-accurate ``a @ b`` where every MXU operand is bf16.

    ``a``: (M, K) fp32; ``b``: (K, N) fp32. Non-multiple shapes are
    zero-padded to the block grid (exact: zero rows/cols contribute 0).
    """
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    a32 = _pad_dim(_pad_dim(a.astype(jnp.float32), 0, bm), 1, bk)
    b32 = _pad_dim(_pad_dim(b.astype(jnp.float32), 0, bk), 1, bn)
    Mp, Kp = a32.shape
    _, Np = b32.shape

    out = pl.pallas_call(
        _kernel,
        grid=(Mp // bm, Np // bn, Kp // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a32, b32)
    return out[:M, :N]
