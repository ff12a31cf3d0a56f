"""Pallas TPU kernel: pooled two-sided block VMM with in-pass TR dot.

Paper mapping (RePAST Sec. V): the mapping scheme wires INV crossbar
groups directly into the weight-update VMM crossbars, so the SOI
inverse feeds ``dW = A^{-1} (dL/dW) G^{-1}`` (Eqn. 3) without a
round-trip through memory. The TPU image: the WU plan pools every
factored gradient tile of the network into same-``(bi, bo)`` batches,
and this kernel runs the whole pool as one program — per grid step the
tile's ``A_inv``/``G_inv`` blocks and the gradient tile meet in VMEM,
both VMMs run back-to-back (the intermediate never leaves VMEM — the
fused-crossbar-group analogue), and the fp32 trust-region contribution
``sum(out * g)`` is accumulated *in the same pass*, so the KL clip
needs no second traversal of the full gradient.

Every matmul is the hi/lo "bit-sliced" product (``bitslice_mm``'s
three-partial scheme): the MXU only ever sees bf16 operands, fp32
accumulation plays the S+A unit — the paper's high-precision-from-
low-precision-cells claim transposed to TPU.

Grid: one program per pooled tile; dims are multiples of 128 (padded)
so both dots hit the MXU at full tile occupancy.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantize import hilo_matmul
from repro.kernels.vmem import VMEM_LIMIT_BYTES

__all__ = ["fused_precond"]


def _kernel(a_ref, g_ref, gi_ref, o_ref, dot_ref):
    g = g_ref[0]
    # left VMM (A-side INV feed), intermediate stays in VMEM
    tmp = hilo_matmul(a_ref[0], g)
    # right VMM (G-side INV feed)
    out = hilo_matmul(tmp, gi_ref[0])
    o_ref[0] = out
    # trust-region contribution of this tile, same pass: gradient pad
    # rows/cols are zero, so the padded dot equals the unpadded one.
    # Written across one (1, 128) lane row: a scalar block would not
    # satisfy the TPU's (8, 128) block tiling.
    dot_ref[...] = jnp.full(dot_ref.shape, jnp.sum(out * g), jnp.float32)


def _pad2(x, r, c):
    pr, pc = r - x.shape[-2], c - x.shape[-1]
    if pr == 0 and pc == 0:
        return x
    return jnp.pad(x, [(0, 0), (0, pr), (0, pc)])


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_precond(
    a_inv: jax.Array,
    g: jax.Array,
    g_inv: jax.Array,
    *,
    interpret: bool = False,
):
    """Batched ``out[n] = A_inv[n] @ g[n] @ G_inv[n]`` + pooled TR dot.

    ``a_inv``: (N, bi, bi); ``g``: (N, bi, bo); ``g_inv``: (N, bo, bo),
    all fp32 (bi, bo <= 1024, padded to multiples of 128 internally —
    zero pads, exact). Returns ``(out, dots)``: (N, bi, bo) fp32
    preconditioned tiles and (N,) fp32 per-tile ``sum(out * g)`` —
    ``dots.sum()`` is the pool's trust-region mass, computed without a
    second gradient traversal.
    """
    n, bi, bo = g.shape
    bi_p = max(128, (-(-bi // 128)) * 128)
    bo_p = max(128, (-(-bo // 128)) * 128)
    a_p = _pad2(a_inv.astype(jnp.float32), bi_p, bi_p)
    g_p = _pad2(g.astype(jnp.float32), bi_p, bo_p)
    gi_p = _pad2(g_inv.astype(jnp.float32), bo_p, bo_p)

    out, dots = pl.pallas_call(
        _kernel,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, bi_p, bi_p), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, bi_p, bo_p), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, bo_p, bo_p), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bi_p, bo_p), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, 128), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, bi_p, bo_p), jnp.float32),
            jax.ShapeDtypeStruct((n, 1, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(a_p, g_p, gi_p)
    return out[:, :bi, :bo], dots[:, 0, 0]
