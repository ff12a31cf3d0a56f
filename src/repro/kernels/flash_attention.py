"""Pallas TPU kernel: causal self-attention by blocked online softmax
(flash attention), forward and backward.

The XLA path (``models/layers.attention``) builds the whole (T x S) f32
score tensor, its mask, softmax and bf16 probabilities in HBM, forward
and backward. Here the scores of one (block x block) tile live in VMEM
only. The forward keeps a running max and sum per query row and
rescales its output accumulator as each KV block arrives; it saves the
rows' logsumexp. The backward recomputes each tile's probabilities from
it: one kernel accumulates dq over the KV blocks of a query block,
another dk and dv over the query blocks (and, for GQA, the query heads)
of a KV block. Blocks above the diagonal are skipped, their copy (the
index maps clamp to the last block needed) and their compute; only the
diagonal blocks build the mask.

The value heads may be narrower than the query/key heads (latent
attention: 192 and 128); the scale is the query/key width's.

Numerics are the XLA path's: bf16 operands with f32 accumulation, the
``hd ** -0.5`` scale applied to the f32 scores, f32 softmax statistics,
probabilities cast to the value dtype for PV (the backward casts
probabilities and scaled score gradients to the operand dtype for its
products). The backward's row term ``sum_s p_s dp_s`` is taken against
the forward's f32 output, before its rounding to bf16: against the
rounded output it would carry that rounding as a bias into every score
gradient of the row, which no longer sum to zero, and the gradients of
parameters that depend on that cancellation (the key bias under rotary
embeddings) would drift.

GQA reads each KV head once per query head of its group, through the
index maps: K/V are never repeated in HBM. The kernel is one device's:
``layers.attention`` takes it only where no mesh of several devices is
in scope.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.vmem import VMEM_LIMIT_BYTES

__all__ = ["MIN_BLOCK", "block_size", "causal_flash_attention", "supports"]

#: the kernel's smallest block along the sequence (one lane tile); the
#: row statistics are stored across this many lanes
MIN_BLOCK = 128
#: the largest block ``block_size`` picks (chip sweep: PERF.md, PR 14)
MAX_BLOCK = 1024
#: the score of a masked entry (the XLA path's)
_MASKED = -1e30
_F32 = jnp.float32
_NT = (((1,), (1,)), ((), ()))            # a @ b.T
#: the last grid axis walks the blocks a scratch accumulates over
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=VMEM_LIMIT_BYTES)


def supports(T: int, S: int) -> bool:
    """Whether the kernel takes a causal self-attention of these lengths:
    equal query and key lengths, a whole number of smallest blocks."""
    return T == S and T % MIN_BLOCK == 0


def block_size(T: int) -> int:
    """The largest multiple of ``MIN_BLOCK`` up to ``MAX_BLOCK`` that
    divides T: the tile of queries and of keys in all three kernels."""
    b = max(MIN_BLOCK, min(MAX_BLOCK, T) // MIN_BLOCK * MIN_BLOCK)
    while T % b:
        b -= MIN_BLOCK
    return b


def _lanes(x, n):
    """A (rows, 128) lane-replicated statistic as (rows, n)."""
    reps = -(-n // MIN_BLOCK)
    return (jnp.tile(x, (1, reps)) if reps > 1 else x)[:, :n]


def _column(row):
    """A (1, n) row statistic as (n, 128), replicated along the lanes."""
    return jnp.broadcast_to(row, (MIN_BLOCK, row.shape[1])).T


def _scores(a, b, scale, masked, keys_on_rows=False):
    """(a @ b.T) * scale in f32. Where ``masked`` (a tile on the
    diagonal), the pairs whose key comes after its query take the masked
    score; queries run along the rows and keys along the columns, or the
    other way round with ``keys_on_rows``."""
    s = lax.dot_general(a, b, _NT, preferred_element_type=_F32) * scale
    if masked:
        rows = lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(rows <= cols if keys_on_rows else cols <= rows, s,
                      _MASKED)
    return s


def _on_blocks(i, j, body):
    """Run ``body(masked)`` for query block ``i`` and key block ``j``:
    masked on the diagonal, plain below it, skipped above it."""
    pl.when(j == i)(lambda: body(True))
    pl.when(j < i)(lambda: body(False))


# -- forward -------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc,
                *, scale, n):
    i, j = pl.program_id(2), pl.program_id(3)
    bk, hd = k_ref.shape[0], acc_sc.shape[-1]

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, _MASKED, _F32)
        l_sc[...] = jnp.zeros(l_sc.shape, _F32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, _F32)

    def body(masked):
        v = v_ref[...]
        s = _scores(q_ref[...], k_ref[...], scale, masked)
        m_prev = m_sc[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_next, bk))
        alpha = jnp.exp(m_prev - m_next)
        l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=1, keepdims=True)
        m_sc[...] = m_next
        pv = lax.dot(p.astype(v.dtype), v, preferred_element_type=_F32)
        acc_sc[...] = _lanes(alpha, hd) * acc_sc[...] + pv

    _on_blocks(i, j, body)

    @pl.when(j == n - 1)
    def _():
        l = l_sc[...]
        o_ref[...] = acc_sc[...] / _lanes(l, hd)
        # one row per query block: (b, 128) replicated -> (1, b)
        lse_ref[...] = (m_sc[...] + jnp.log(l)).T[:1]


def _spec(rows, cols, index_map):
    """A (rows, cols) block of one (batch, head) of a (B, H, T, d)
    operand."""
    return pl.BlockSpec((None, None, rows, cols), index_map)


def _fwd(q, k, v, b, interpret):
    """q/k: (B, H|Hkv, T, hd); v: (B, Hkv, T, dv). Returns the f32 output
    (B, H, T, dv) and the rows' logsumexp, (B, H, 1, T)."""
    B, H, T, hd = q.shape
    dv = v.shape[-1]
    g, n = H // k.shape[1], T // b

    def q_map(bi, h, i, j):
        return bi, h, i, 0

    def kv_map(bi, h, i, j):
        # past the diagonal: the last block needed, so no copy is made
        return bi, h // g, jnp.minimum(j, i), 0

    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=hd ** -0.5, n=n),
        grid=(B, H, n, n),
        in_specs=[_spec(b, hd, q_map), _spec(b, hd, kv_map),
                  _spec(b, dv, kv_map)],
        out_specs=[_spec(b, dv, q_map),
                   _spec(1, b, lambda bi, h, i, j: (bi, h, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((B, H, T, dv), _F32),
                   jax.ShapeDtypeStruct((B, H, 1, T), _F32)],
        scratch_shapes=[pltpu.VMEM((b, MIN_BLOCK), _F32),
                        pltpu.VMEM((b, MIN_BLOCK), _F32),
                        pltpu.VMEM((b, dv), _F32)],
        compiler_params=_PARAMS, interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)


# -- backward ------------------------------------------------------------
#
# The row statistics arrive as rows, (1, T) per head: logsumexp and
# ``di = sum_d o * do``. The dq kernel, whose tiles run queries down the
# rows, turns its block's into lane-replicated columns once; the dkv
# kernel runs keys down the rows and uses them as rows, so none of its
# tiles is transposed. The score gradients are scaled in f32 before
# their cast, as the XLA path's are.


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref,
               lse_sc, di_sc, dq_sc, *, scale, n):
    i, j = pl.program_id(2), pl.program_id(3)
    bk = k_ref.shape[0]

    @pl.when(j == 0)
    def _():
        lse_sc[...] = _column(lse_ref[...])
        di_sc[...] = _column(di_ref[...])
        dq_sc[...] = jnp.zeros(dq_sc.shape, _F32)

    def body(masked):
        k = k_ref[...]
        s = _scores(q_ref[...], k, scale, masked)
        p = jnp.exp(s - _lanes(lse_sc[...], bk))
        dp = lax.dot_general(do_ref[...], v_ref[...], _NT,
                             preferred_element_type=_F32)
        ds = p * (dp - _lanes(di_sc[...], bk)) * scale
        dq_sc[...] += lax.dot(ds.astype(k.dtype), k,
                              preferred_element_type=_F32)

    _on_blocks(i, j, body)

    @pl.when(j == n - 1)
    def _():
        dq_ref[...] = dq_sc[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref,
                dv_ref, dk_sc, dv_sc, *, scale, n, nr):
    j, r = pl.program_id(2), pl.program_id(3)

    @pl.when(r == 0)
    def _():
        dk_sc[...] = jnp.zeros(dk_sc.shape, _F32)
        dv_sc[...] = jnp.zeros(dv_sc.shape, _F32)

    def body(masked):
        q, do = q_ref[...], do_ref[...]
        # keys down the rows, queries along the lanes
        s = _scores(k_ref[...], q, scale, masked, keys_on_rows=True)
        p = jnp.exp(s - lse_ref[...])
        dv_sc[...] += lax.dot(p.astype(do.dtype), do,
                              preferred_element_type=_F32)
        dp = lax.dot_general(v_ref[...], do, _NT,
                             preferred_element_type=_F32)
        ds = p * (dp - di_ref[...]) * scale
        dk_sc[...] += lax.dot(ds.astype(q.dtype), q,
                              preferred_element_type=_F32)

    _on_blocks(r % n, j, body)

    @pl.when(r == nr - 1)
    def _():
        dk_ref[...] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


def _bwd(q, k, v, do, lse, di, b, interpret):
    B, H, T, hd = q.shape
    hkv, dv = k.shape[1], v.shape[-1]
    g, n = H // hkv, T // b
    scale = hd ** -0.5

    def q_map(bi, h, i, j):
        return bi, h, i, 0

    def stat_map(bi, h, i, j):
        return bi, h, 0, i

    def kv_map(bi, h, i, j):
        return bi, h // g, jnp.minimum(j, i), 0

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, n=n),
        grid=(B, H, n, n),
        in_specs=[_spec(b, hd, q_map), _spec(b, hd, kv_map),
                  _spec(b, dv, kv_map), _spec(b, dv, q_map),
                  _spec(1, b, stat_map), _spec(1, b, stat_map)],
        out_specs=_spec(b, hd, q_map),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((b, MIN_BLOCK), _F32),
                        pltpu.VMEM((b, MIN_BLOCK), _F32),
                        pltpu.VMEM((b, hd), _F32)],
        compiler_params=_PARAMS, interpret=interpret,
        name="flash_attention_dq",
    )(q, k, v, do, lse, di)

    nr = g * n                  # the group's query heads, then blocks

    def qd_i(j, r):
        # before the diagonal: the first block needed, so no copy is made
        return jnp.maximum(r % n, j)

    def qd_map(bi, h, j, r):
        return bi, h * g + r // n, qd_i(j, r), 0

    def sd_map(bi, h, j, r):
        return bi, h * g + r // n, 0, qd_i(j, r)

    def kd_map(bi, h, j, r):
        return bi, h, j, 0

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, n=n, nr=nr),
        grid=(B, hkv, n, nr),
        in_specs=[_spec(b, hd, qd_map), _spec(b, hd, kd_map),
                  _spec(b, dv, kd_map), _spec(b, dv, qd_map),
                  _spec(1, b, sd_map), _spec(1, b, sd_map)],
        out_specs=[_spec(b, hd, kd_map), _spec(b, dv, kd_map)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((b, hd), _F32),
                        pltpu.VMEM((b, dv), _F32)],
        compiler_params=_PARAMS, interpret=interpret,
        name="flash_attention_dkv",
    )(q, k, v, do, lse, di)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, block, interpret):
    return _fwd(q, k, v, block, interpret)[0].astype(q.dtype)


def _flash_fwd(q, k, v, block, interpret):
    o, lse = _fwd(q, k, v, block, interpret)
    return o.astype(q.dtype), (q, k, v, o, lse)


def _flash_bwd(block, interpret, res, do):
    q, k, v, o, lse = res
    # sum_s p_s dp_s of each row, against the unrounded output, as a row
    di = jnp.sum(o * do.astype(_F32), axis=-1)[:, :, None, :]
    return _bwd(q, k, v, do, lse, di, block, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def causal_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           block: Optional[int] = None,
                           interpret: bool = False) -> jax.Array:
    """Causal self-attention: q/k (B, T, H|Hkv, hd), v (B, T, Hkv, dv),
    with ``supports(T, T)`` and ``H % Hkv == 0``; returns (B, T, H, dv)
    in q's dtype. ``block`` (queries and keys per tile, dividing T)
    defaults to ``block_size(T)``."""
    T = q.shape[1]
    # (B, T, H, hd) -> (B, H, T, hd): the kernel's head-major layout
    q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out = _flash(q, k, v, block or block_size(T), interpret)
    return out.transpose(0, 2, 1, 3)
