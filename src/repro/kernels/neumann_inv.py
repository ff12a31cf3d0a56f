"""Pallas TPU kernel: VMEM-resident composed-precision matrix inverse.

Paper mapping (RePAST Sec. III): the analog INV crossbar holds ``A_H``
(top bits) and settles to ``A_H^{-1} b`` in O(1) *without any memory
traffic* — the whole solve happens inside the array. The TPU analogue of
"inside the array" is VMEM: this kernel pins the entire (damped) SOI
block (n <= 1024, the paper's max INV-group size) in VMEM and runs the
full composed-precision inversion there —

  1. hi/lo split   ``A = A_H + A_L``    (bf16 "cells", Sec. III-A.3)
  2. Newton–Schulz on ``A_H``           (the low-precision INV primitive)
  3. Loop A        Neumann series over ``A_L``  (Eqn. 9)
  4. Loop x        iterative refinement vs the full ``A``

— with *zero* HBM round-trips between the O(n^3) iterations. A
stock-XLA implementation streams each matmul's operands HBM<->VMEM
(3 * 2n^2 * 4B per matmul * ~30 matmuls); for n=1024 that is ~1 GB of
avoidable HBM traffic per block inverse, which matters because the SOI
refresh inverts hundreds of blocks (this is the memory-roofline
argument; see EXPERIMENTS.md §Perf).

Grid: one program per (batch of) block(s); each program owns the whole
(n, n) problem in VMEM. Matmul dims are multiples of 128 (n is padded),
so every dot hits the MXU at full tile occupancy.

Every matmul inside the loop body is an explicit hi/lo "bit-sliced"
product (see ``bitslice_mm``): the MXU never sees an fp32 operand, which
is the paper's claim transposed to TPU — high-precision inversion out of
low-precision primitives only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantize import (
    hilo_matmul, hilo_matmul_exact_lhs, split_hi_lo_bf16)
from repro.kernels.vmem import VMEM_LIMIT_BYTES

__all__ = ["neumann_inv"]


def _kernel(a_ref, damp_ref, o_ref, *, n, ns_iters, taylor_terms,
            refine_steps):
    eye = jnp.eye(n, dtype=jnp.float32)
    # Damped block: A + lam*I (Tikhonov, paper Sec. III-A.3). Padding rows
    # get the identity so the padded block stays invertible.
    a = a_ref[0] + jnp.max(damp_ref[0]) * eye
    a_hi16, a_lo16 = split_hi_lo_bf16(a)
    a_hi = a_hi16.astype(jnp.float32)

    # ||A||_2 upper bound: sqrt(||A||_1 ||A||_inf); X0 = A_H / bound^2.
    n1 = jnp.max(jnp.sum(jnp.abs(a_hi), axis=0))
    ninf = jnp.max(jnp.sum(jnp.abs(a_hi), axis=1))
    x = a_hi / (n1 * ninf)

    # (2) low-precision INV primitive: Newton-Schulz  X <- X(2I - A_H X)
    # (A_H exactly bf16 => two-partial products, §Perf 3.1)
    def ns_body(_, x):
        ax = hilo_matmul_exact_lhs(a_hi16, x)
        return hilo_matmul(x, 2.0 * eye - ax)

    x = jax.lax.fori_loop(0, ns_iters, ns_body, x)

    # (3) Loop A: Neumann series  M = sum_l (-Y A_L)^l Y   (Eqn. 9)
    def taylor_body(_, carry):
        m, t = carry
        t = -hilo_matmul(x, hilo_matmul_exact_lhs(a_lo16, t))
        return m + t, t

    m, _ = jax.lax.fori_loop(0, max(taylor_terms - 1, 0), taylor_body,
                             (x, x))

    # (4) Loop x analogue: refinement against the full-precision A.
    def refine_body(_, m):
        r = eye - hilo_matmul(a, m)
        return m + hilo_matmul(m, r)

    m = jax.lax.fori_loop(0, refine_steps, refine_body, m)
    o_ref[0] = m


def _pad_block(a: jax.Array, n_pad: int) -> jax.Array:
    """Pad (..., n, n) blocks to (..., n_pad, n_pad) with identity tails
    (keeps the padded block SPD and its inverse block-diagonal)."""
    n = a.shape[-1]
    if n == n_pad:
        return a
    pad = n_pad - n
    widths = [(0, 0)] * (a.ndim - 2) + [(0, pad), (0, pad)]
    a = jnp.pad(a, widths)
    eye_tail = jnp.pad(jnp.eye(pad, dtype=a.dtype),
                       [(n, 0), (n, 0)])
    return a + eye_tail


@functools.partial(
    jax.jit,
    static_argnames=("ns_iters", "taylor_terms", "refine_steps",
                     "interpret"))
def neumann_inv(
    a: jax.Array,
    damping: jax.Array,
    *,
    ns_iters: int = 14,
    taylor_terms: int = 4,
    refine_steps: int = 1,
    interpret: bool = False,
) -> jax.Array:
    """Composed-precision inverse of damped SPD blocks, VMEM-resident.

    ``a``: (nb, n, n) fp32 SPD blocks (n <= 1024).
    ``damping``: (nb,) per-block Tikhonov level.
    Returns (nb, n, n) fp32 ``(a + damping I)^{-1}``.
    """
    nb, n, _ = a.shape
    n_pad = max(128, (-(-n // 128)) * 128)
    a_p = _pad_block(a.astype(jnp.float32), n_pad)
    damp = jnp.asarray(damping, jnp.float32)
    if damp.size == 1:
        # scalar damping: one Tikhonov level for every block (the
        # docstring's per-block-or-scalar contract; a bare reshape to
        # (nb, 1) crashes for nb > 1)
        damp = jnp.broadcast_to(damp.reshape(()), (nb,))
    elif damp.shape != (nb,):
        raise ValueError(
            f"damping must be a scalar or shape ({nb},) to match the "
            f"{nb} blocks; got shape {damp.shape}")
    # one (1, 128) lane row per block: a scalar block would not satisfy
    # the TPU's (8, 128) block tiling
    damp = jnp.broadcast_to(damp.reshape(nb, 1, 1), (nb, 1, 128))

    out = pl.pallas_call(
        functools.partial(_kernel, n=n_pad, ns_iters=ns_iters,
                          taylor_terms=taylor_terms,
                          refine_steps=refine_steps),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, n_pad, n_pad), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, 128), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, n_pad, n_pad), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, n_pad, n_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(a_p, damp)
    return out[:, :n, :n]
