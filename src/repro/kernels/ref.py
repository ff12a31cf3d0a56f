"""Pure-jnp oracles for the Pallas kernels (allclose targets in tests).

Each oracle implements the *same algorithm* at the same working
precision as its kernel (hi/lo bf16 partial products, identical
iteration counts), so kernels must match to float-associativity-level
tolerance; a second set of fp64-ish references bounds the *algorithmic*
error (what the composed-precision scheme is supposed to achieve).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.quantize import (
    hilo_matmul,
    hilo_matmul_exact_lhs,
    split_hi_lo_bf16,
)


def bitslice_mm_ref(a: jax.Array, b: jax.Array) -> jax.Array:
    """Oracle for kernels.bitslice_mm: identical 3-partial hi/lo product."""
    return hilo_matmul(a.astype(jnp.float32), b.astype(jnp.float32))


def _norm_bound_hi(a_hi: jax.Array) -> jax.Array:
    n1 = jnp.max(jnp.sum(jnp.abs(a_hi), axis=-2))
    ninf = jnp.max(jnp.sum(jnp.abs(a_hi), axis=-1))
    return n1 * ninf


def neumann_inv_ref(a: jax.Array, damping: jax.Array, *,
                    ns_iters: int = 14, taylor_terms: int = 4,
                    refine_steps: int = 1) -> jax.Array:
    """Oracle for kernels.neumann_inv on (nb, n, n) blocks."""

    def one(a1, lam):
        n = a1.shape[-1]
        eye = jnp.eye(n, dtype=jnp.float32)
        ad = a1.astype(jnp.float32) + lam * eye
        a_hi16, a_lo16 = split_hi_lo_bf16(ad)
        a_hi = a_hi16.astype(jnp.float32)
        x = a_hi / _norm_bound_hi(a_hi)

        def ns(_, x):
            return hilo_matmul(
                x, 2.0 * eye - hilo_matmul_exact_lhs(a_hi16, x))

        x = jax.lax.fori_loop(0, ns_iters, ns, x)

        def taylor(_, carry):
            m, t = carry
            t = -hilo_matmul(x, hilo_matmul_exact_lhs(a_lo16, t))
            return m + t, t

        m, _ = jax.lax.fori_loop(0, max(taylor_terms - 1, 0), taylor,
                                 (x, x))

        def refine(_, m):
            return m + hilo_matmul(m, eye - hilo_matmul(ad, m))

        return jax.lax.fori_loop(0, refine_steps, refine, m)

    return jax.vmap(one)(a, jnp.asarray(damping, jnp.float32))


def fused_gram_inv_ref(a: jax.Array, *, rel_damp: float = 0.03,
                       ns_iters: int = 14, taylor_terms: int = 4,
                       refine_steps: int = 1) -> jax.Array:
    """Oracle for kernels.fused_gram_inv.

    ``a``: (T, nb, n). Materializes the hi/lo Gram (same partial-product
    set as the kernel), then applies neumann_inv_ref's iteration.
    """
    t = a.shape[0]
    a32 = a.astype(jnp.float32)
    a_hi, a_lo = split_hi_lo_bf16(a32)

    def mm_t(x, y):
        return jnp.einsum("tbn,tbm->bnm", x.astype(jnp.float32),
                          y.astype(jnp.float32))

    gram = (mm_t(a_hi, a_hi) + mm_t(a_hi, a_lo) + mm_t(a_lo, a_hi)) \
        / jnp.float32(t)
    n = gram.shape[-1]
    lam = rel_damp * jnp.trace(gram, axis1=-2, axis2=-1) / n + 1e-8
    return neumann_inv_ref(gram, lam, ns_iters=ns_iters,
                           taylor_terms=taylor_terms,
                           refine_steps=refine_steps)


def fused_precond_ref(a_inv: jax.Array, g: jax.Array,
                      g_inv: jax.Array):
    """Oracle for kernels.fused_precond: identical hi/lo partial-product
    set for both VMMs (left-first association, like
    ``soi.two_sided_block_vmm``) and the same-pass fp32 tile dots."""
    def one(a1, g1, gi1):
        tmp = hilo_matmul(a1.astype(jnp.float32), g1.astype(jnp.float32))
        out = hilo_matmul(tmp, gi1.astype(jnp.float32))
        return out, jnp.sum(out * g1.astype(jnp.float32))

    return jax.vmap(one)(a_inv, g, g_inv)


def smw_update_ref(inv: jax.Array, v: jax.Array, *, decay: float,
                   cscale: float) -> jax.Array:
    """Oracle for kernels.smw_update: the identical padded two-pass
    pipeline — per-block hi/lo partial products in the same order the
    interpreted grid executes them, and the *same* batched k x k solve
    expression between passes — so the kernel matches it to fp32
    reassociation error."""
    n, k, bs = v.shape
    bs_p = max(128, (-(-bs // 128)) * 128)
    k_p = max(128, (-(-k // 128)) * 128)

    def pad2(x, r, c):
        return jnp.pad(x, [(0, 0), (0, r - x.shape[-2]),
                           (0, c - x.shape[-1])])

    inv_p = pad2(inv.astype(jnp.float32), bs_p, bs_p)
    v_p = pad2(v.astype(jnp.float32), k_p, bs_p)
    inv_decay = jnp.float32(0.5 / decay)
    ms, ys, ss = [], [], []
    for i in range(n):
        m1 = (inv_p[i] + inv_p[i].T) * inv_decay
        y1 = hilo_matmul(v_p[i], m1)
        ms.append(m1)
        ys.append(y1)
        ss.append(hilo_matmul(y1, v_p[i].T))
    y = jnp.stack(ys)
    s_full = jnp.stack(ss) + jnp.eye(k_p, dtype=jnp.float32) \
        / jnp.float32(cscale)
    z = jnp.linalg.solve(s_full, y)
    out = jnp.stack([ms[i] - hilo_matmul(ys[i].T, z[i])
                     for i in range(n)])
    return out[:, :bs, :bs]


def exact_smw_update(inv: jax.Array, v: jax.Array, *, decay: float,
                     cscale: float) -> jax.Array:
    """fp32 einsum reference bounding the bit-sliced kernel's error
    (the same math ``solve.smw.smw_update_flat`` runs on the jnp path)."""
    k = v.shape[-2]
    m = (inv + jnp.swapaxes(inv, -1, -2)) * jnp.float32(0.5 / decay)
    y = jnp.einsum("nkb,nbc->nkc", v.astype(jnp.float32), m)
    s = jnp.einsum("nkb,nlb->nkl", y, v.astype(jnp.float32)) \
        + jnp.eye(k, dtype=jnp.float32) / jnp.float32(cscale)
    z = jnp.linalg.solve(s, y)
    return m - jnp.einsum("nka,nkb->nab", y, z)


def exact_two_sided(a_inv: jax.Array, g: jax.Array,
                    g_inv: jax.Array) -> jax.Array:
    """fp32 linalg reference bounding the bit-sliced kernel's error."""
    return jnp.einsum("nab,nbc,ncd->nad", a_inv.astype(jnp.float32),
                      g.astype(jnp.float32), g_inv.astype(jnp.float32))


def exact_gram_inv(a: jax.Array, rel_damp: float = 0.03) -> jax.Array:
    """fp32 linalg reference for the *algorithmic* accuracy bound."""
    t = a.shape[0]
    gram = jnp.einsum("tbn,tbm->bnm", a.astype(jnp.float32),
                      a.astype(jnp.float32)) / jnp.float32(t)
    n = gram.shape[-1]
    lam = rel_damp * jnp.trace(gram, axis1=-2, axis2=-1) / n + 1e-8
    eye = jnp.eye(n, dtype=jnp.float32)
    return jnp.linalg.inv(gram + lam[:, None, None] * eye)
