"""Grouped matrix products over rows sorted by group: the dropless expert
layer's matmuls and its per-expert K-FAC Grams.

``lhs`` rows are sorted by group; ``sizes`` (int32, ``groups + 1``)
counts each group's rows, and its last entry counts the rows that belong
to no group here (assignments to experts another device holds, and the
padding to a whole tile). Only the first ``groups`` groups are computed:

- ``gmm(lhs (m, k), rhs (groups, k, n), sizes) -> (m, n)``: row ``t`` of
  group ``e`` is ``lhs[t] @ rhs[e]``; rows of no group are zero.
- ``group_gram(a (m, d), sizes, bs) -> (groups, nb, bs, bs)``: per group
  the diagonal ``bs``-blocks of ``sum_t a_t a_t^T`` over its rows.

On a TPU both lower to the megablox Pallas kernels
(``jax.experimental.pallas.ops.tpu.megablox``), whose grid walks only
the row tiles the groups fill (the sizes are scalar-prefetched), so rows
of no group cost nothing; ``gmm``'s backward is megablox's own (a
transposed ``gmm`` for the rows, ``tgmm`` for the weights). Every other
platform takes ``jax.lax.ragged_dot``. Operands are bf16 with f32
accumulation; products are cast to the operands' dtype, Grams are f32.
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp

from repro.core import soi

# the modules themselves (the package exports a function named ``gmm``)
megablox = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.megablox.gmm")
megablox_ops = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.megablox.ops")

__all__ = ["ROW_TILE", "gmm", "group_gram"]

#: rows per tile of the TPU kernels; ``lhs`` rows are a multiple of it
ROW_TILE = 512


def _tiles(m: int, k: int, n: int):
    """Tile sizes (rows, contraction, columns) of the TPU kernels: one
    row tile, a 512-wide contraction, and the whole output width up to
    1,536 columns (1,408-wide experts in one tile), else 512; each
    kernel's double-buffered blocks stay under v5e's 16 MiB default
    scoped VMEM."""
    del m
    return ROW_TILE, min(512, k), n if n <= 1536 else 512


def _gram_tiles(m: int, k: int, n: int):
    del m
    return ROW_TILE, min(512, k), min(512, n)


def _gmm_tpu(lhs, rhs, sizes):
    return megablox_ops.gmm(lhs, rhs, sizes, lhs.dtype, _tiles)


def _gmm_xla(lhs, rhs, sizes):
    return jax.lax.ragged_dot(lhs, rhs, sizes[:rhs.shape[0]],
                              preferred_element_type=jnp.float32
                              ).astype(lhs.dtype)


def gmm(lhs: jax.Array, rhs: jax.Array, sizes: jax.Array) -> jax.Array:
    """Grouped product (module doc); ``rhs`` is cast to ``lhs``'s dtype."""
    rhs = rhs.astype(lhs.dtype)
    return jax.lax.platform_dependent(lhs, rhs, sizes, tpu=_gmm_tpu,
                                      default=_gmm_xla)


def _gram_tpu(a, sizes, groups):
    return megablox.tgmm(a.swapaxes(0, 1), a, sizes, jnp.float32,
                         _gram_tiles, num_actual_groups=groups)


def _gram_xla(a, sizes, groups):
    dn = jax.lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(([0], [0]), ([], [])),
        lhs_ragged_dimensions=[0], rhs_group_dimensions=[])
    return jax.lax.ragged_dot_general(a, a, sizes[:groups], dn,
                                      preferred_element_type=jnp.float32)


@jax.named_scope("expert_gram")
def group_gram(a: jax.Array, sizes: jax.Array, bs: int) -> jax.Array:
    """Per group, the diagonal ``bs``-blocks of its rows' Gram (module
    doc), unnormalized: (groups, nb, bs, bs) in f32. The kernels form
    the whole (d x d) Gram of each group and keep its diagonal blocks."""
    groups = sizes.shape[0] - 1
    # a statistic: no derivative (the kernel's dynamic grid has none)
    a = jax.lax.stop_gradient(a)
    full = jax.lax.platform_dependent(
        a, sizes, tpu=lambda a, s: _gram_tpu(a, s, groups),
        default=lambda a, s: _gram_xla(a, s, groups))
    full = soi.pad_to_blocks(soi.pad_to_blocks(full, -1, bs), -2, bs)
    nb = full.shape[-1] // bs
    blocks = full.reshape(groups, nb, bs, nb, bs)
    return jnp.moveaxis(jnp.diagonal(blocks, axis1=1, axis2=3), -1, 1)
