"""Pallas TPU kernel: rank-k SMW inverse update, hi/lo bit-sliced.

Paper mapping: RePAST re-programs the INV crossbars with a freshly
inverted factor once per SOI interval; the incremental alternative
(PANTHER-style rank-k crossbar updates) only needs the Woodbury
correction

    M   = sym(F_inv) / d              (decay-scale, free VMEM reshuffle)
    Y   = V M                         (VMM 1)
    S   = I/c + Y V^T                 (small k x k capacitance)
    out = M - Y^T S^-1 Y              (VMM 2 + outer-product correction)

Per grid step one block's cached inverse and its rank-k columns meet in
VMEM: pass 1 emits ``M``, ``Y`` and the capacitance ``S``; the k x k
solve runs on the host between passes (O(k^3), negligible and LAPACK-
exact); pass 2 applies the outer-product correction without the
intermediates ever leaving VMEM. Both big products are the hi/lo
bit-sliced three-partial scheme of ``fused_precond`` — bf16 operands on
the MXU, fp32 accumulation as the S+A unit.

Padding is exact: ``V`` pad rows are zero, so padded ``Y``/``S`` rows
vanish and the ``I/c`` diagonal keeps the padded capacitance block
invertible (its solve rows come out zero); the unpadded slice is
returned. Grid: one program per block, dims padded to multiples of 128.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantize import hilo_matmul
from repro.kernels.vmem import VMEM_LIMIT_BYTES

__all__ = ["smw_update"]


def _kernel_stats(inv_ref, v_ref, m_ref, y_ref, s_ref, *, inv_decay):
    inv = inv_ref[0]
    m = (inv + inv.T) * inv_decay
    v = v_ref[0]
    y = hilo_matmul(v, m)                 # VMM 1: (k, bs) stays in VMEM
    m_ref[0] = m
    y_ref[0] = y
    s_ref[0] = hilo_matmul(y, v.T)        # capacitance, k x k


def _kernel_apply(m_ref, y_ref, z_ref, o_ref):
    # outer-product correction: VMM 2, intermediates never left VMEM
    o_ref[0] = m_ref[0] - hilo_matmul(y_ref[0].T, z_ref[0])


def _pad2(x, r, c):
    pr, pc = r - x.shape[-2], c - x.shape[-1]
    if pr == 0 and pc == 0:
        return x
    return jnp.pad(x, [(0, 0), (0, pr), (0, pc)])


@functools.partial(jax.jit, static_argnames=("decay", "cscale",
                                             "interpret"))
def smw_update(
    inv: jax.Array,
    v: jax.Array,
    *,
    decay: float,
    cscale: float,
    interpret: bool = False,
):
    """Batched Woodbury update ``inv' = M - (VM)^T S^-1 (VM)``.

    ``inv``: (N, bs, bs) cached inverses of the previous damped factors;
    ``v``: (N, k, bs) rank-k columns; ``decay`` the factor EMA decay and
    ``cscale`` the contribution weight ``c = (1 - decay) * w``. Returns
    (N, bs, bs) fp32 updated inverses of ``decay * F + c * V^T V``
    (to the cached inverse's own accuracy).
    """
    n, k, bs = v.shape
    bs_p = max(128, (-(-bs // 128)) * 128)
    k_p = max(128, (-(-k // 128)) * 128)
    inv_p = _pad2(inv.astype(jnp.float32), bs_p, bs_p)
    v_p = _pad2(v.astype(jnp.float32), k_p, bs_p)

    stats = functools.partial(_kernel_stats,
                              inv_decay=float(0.5 / decay))
    m, y, s = pl.pallas_call(
        stats,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, bs_p, bs_p), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, k_p, bs_p), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bs_p, bs_p), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, k_p, bs_p), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, k_p, k_p), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, bs_p, bs_p), jnp.float32),
            jax.ShapeDtypeStruct((n, k_p, bs_p), jnp.float32),
            jax.ShapeDtypeStruct((n, k_p, k_p), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(inv_p, v_p)

    s_full = s + jnp.eye(k_p, dtype=jnp.float32) / jnp.float32(cscale)
    z = jnp.linalg.solve(s_full, y)    # k x k host solve, LAPACK-exact

    out = pl.pallas_call(
        _kernel_apply,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, bs_p, bs_p), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, k_p, bs_p), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, k_p, bs_p), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bs_p, bs_p), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, bs_p, bs_p), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(m, y, z)
    return out[:, :bs, :bs]
