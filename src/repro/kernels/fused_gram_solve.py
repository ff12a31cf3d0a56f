"""Pallas TPU kernel: fused Gram-accumulate + composed-precision inverse.

Paper mapping (RePAST Sec. IV-B / V-B.1, the MM-INV pattern): the SOI
factor is a Gram ``A = a a^T`` of activations, immediately followed by an
inversion. RePAST's second mapping strategy writes ``a`` itself into the
INV crossbars and lets the analog feedback compute ``(a a^T)^{-1} b``
*without ever materializing A* (Eqn. 11-13, the fused
matrix-multiplication-and-inversion). The win is crossbar occupation
when ``m >> n`` — i.e. memory.

TPU adaptation: the Gram never touches HBM. Activations ``a`` (T, n)
stream through VMEM in (bt, n) tiles; the (n, n) Gram accumulates in a
VMEM scratch across the grid sweep; on the last tile the same program
damps it and runs the whole composed-precision inversion (Newton-Schulz
+ Neumann + refinement, every matmul hi/lo bf16) in place, emitting the
inverse directly. Fusing removes the HBM write+read of the Gram and the
kernel-launch boundary the paper's non-fused strategy pays — the same
trade its Eqn. 15/16 cost model captures.

Grid: (nb, T/bt); the token axis is innermost ("arbitrary") so the Gram
scratch is live across the sweep of one block, then reused for the next
factor block (the block axis maps over independent SOI diagonal blocks).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantize import (
    hilo_matmul, hilo_matmul_exact_lhs, hilo_matmul_tn, split_hi_lo_bf16)
from repro.kernels.vmem import VMEM_LIMIT_BYTES

__all__ = ["fused_gram_inv"]


def _kernel(a_ref, o_ref, gram_ref, *, n, n_true, n_tok, rel_damp,
            ns_iters, taylor_terms, refine_steps):
    @pl.when(pl.program_id(1) == 0)
    def _zero():
        gram_ref[...] = jnp.zeros_like(gram_ref)

    # Gram accumulation: one (bt, n) activation tile -> rank-bt update.
    a_t = a_ref[...]                                 # (bt, n) fp32
    gram_ref[...] += hilo_matmul_tn(a_t, a_t)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _invert():
        eye = jnp.eye(n, dtype=jnp.float32)
        g = gram_ref[...] / jnp.float32(n_tok)
        # per-block Tikhonov: rel * tr/n (+floor), as core/soi.py —
        # n_true, not the padded width (padding columns are zero).
        lam = rel_damp * jnp.trace(g) / jnp.float32(n_true) + 1e-8
        a = g + lam * eye
        a_h16, a_l16 = split_hi_lo_bf16(a)
        a_h = a_h16.astype(jnp.float32)

        n1 = jnp.max(jnp.sum(jnp.abs(a_h), axis=0))
        ninf = jnp.max(jnp.sum(jnp.abs(a_h), axis=1))
        x = a_h / (n1 * ninf)

        def ns_body(_, x):
            ax = hilo_matmul_exact_lhs(a_h16, x)
            return hilo_matmul(x, 2.0 * eye - ax)

        x = jax.lax.fori_loop(0, ns_iters, ns_body, x)

        def taylor_body(_, carry):
            m, t = carry
            t = -hilo_matmul(x, hilo_matmul_exact_lhs(a_l16, t))
            return m + t, t

        m, _ = jax.lax.fori_loop(0, max(taylor_terms - 1, 0),
                                 taylor_body, (x, x))

        def refine_body(_, m):
            r = eye - hilo_matmul(a, m)
            return m + hilo_matmul(m, r)

        m = jax.lax.fori_loop(0, refine_steps, refine_body, m)
        o_ref[0] = m


@functools.partial(
    jax.jit,
    static_argnames=("rel_damp", "bt", "ns_iters", "taylor_terms",
                     "refine_steps", "interpret"))
def fused_gram_inv(
    a: jax.Array,
    *,
    rel_damp: float = 0.03,
    bt: int = 512,
    ns_iters: int = 14,
    taylor_terms: int = 4,
    refine_steps: int = 1,
    interpret: bool = False,
) -> jax.Array:
    """Fused ``(a_i^T a_i / T + lam_i I)^{-1}`` per feature block.

    ``a``: (T, nb, n) activations already split into ``nb`` feature slabs
    of width ``n`` (n <= 1024, multiple-of-128 padded internally).
    Returns (nb, n, n) fp32 inverses — the K-FAC A-factor inverse,
    computed without materializing any Gram in HBM.
    """
    t, nb, n = a.shape
    if bt % 8:
        raise ValueError(f"bt={bt} must be a multiple of 8 (the TPU's "
                         f"sublane tiling)")
    n_pad = max(128, (-(-n // 128)) * 128)
    t_pad = (-t) % bt
    a_p = jnp.pad(a.astype(jnp.float32),
                  [(0, t_pad), (0, 0), (0, n_pad - n)])
    # padded feature columns produce zero Gram rows/cols; identity-damp
    # them inside the kernel via lam*I so the block stays invertible.
    tp = a_p.shape[0]
    # (T, nb, n) -> (T, nb * n) is free (row-major), and block i of the
    # columns is feature slab i: a (bt, n_pad) block tiles as (8, 128)
    a_p = a_p.reshape(tp, nb * n_pad)

    out = pl.pallas_call(
        functools.partial(_kernel, n=n_pad, n_true=n, n_tok=t,
                          rel_damp=rel_damp, ns_iters=ns_iters,
                          taylor_terms=taylor_terms,
                          refine_steps=refine_steps),
        grid=(nb, tp // bt),
        in_specs=[
            pl.BlockSpec((bt, n_pad), lambda i, k: (k, i)),
        ],
        out_specs=pl.BlockSpec((1, n_pad, n_pad), lambda i, k: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, n_pad, n_pad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n_pad, n_pad), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(a_p)
    return out[:, :n, :n]
