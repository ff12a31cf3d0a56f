"""Second-order information (SOI) factor layout.

K-FAC factors each layer's Fisher block into two Kronecker factors
``A = E[a a^T]`` (input side) and ``G = E[g g^T]`` (output side) — paper
Sec. II-A. Like RePAST, we approximate each factor block-diagonally with a
configurable block size (the paper's INV-crossbar group supports blocks up
to 1024x1024; Fig. 1/13 study the block-size trade-off), store only the
diagonal blocks, and shard the block dimension across the `model` mesh
axis — the TPU analogue of distributing blocks over INV crossbar groups.

Shapes
------
A linear layer with weight ``(*stack, d_in, d_out)`` (``stack`` are scan /
expert dims) owns:
  A        (*stack, nb_in,  bs, bs)
  G        (*stack, nb_out, bs, bs)
  A_inv / G_inv    same shapes
Gradients are preconditioned block-diagonally:
  dW[i*bs:(i+1)*bs, j*bs:(j+1)*bs] = A_inv[i] @ g[i, j] @ G_inv[j]
"""

from __future__ import annotations

import dataclasses
import math
from typing import Mapping, Tuple

import jax
import jax.numpy as jnp

from repro.core import quantize
from repro.dist.api import shard_hint


@dataclasses.dataclass(frozen=True)
class LinearSpec:
    """A K-FAC-factored linear layer registered by a model.

    ``name`` must equal the '/'-joined path of the weight inside the model
    params pytree, so the optimizer can match gradients to factors.
    """

    d_in: int
    d_out: int
    stack: Tuple[int, ...] = ()     # leading stacked dims, e.g. (L,) or (L, E)
    # Whether this weight's input activations already include the shared
    # input of a sibling (e.g. q/k/v share A). If set, A stats/inverse are
    # read from `share_a_with` instead of being stored.
    share_a_with: str | None = None
    # Tap token dim is the MoE dispatch capacity rather than the raw
    # token count (per-expert buffers).
    cap_tokens: bool = False
    # Per-expert factors over ragged token groups (the dropless layer,
    # models/moe.share_ffn): the collected A is already the per-expert
    # Gram over the expert's own token count, and the tap is the size of
    # the G factor, its cotangent the per-expert G Gram itself.
    grouped: bool = False


def n_blocks(d: int, bs: int) -> int:
    return -(-d // bs)


def leaf_block_count(shape: Tuple[int, ...]) -> int:
    """Total diagonal blocks in one factor leaf ``(*stack, nb, bs, bs)``
    — the unit the block-parallel solver (repro.solve) distributes over
    mesh devices (the paper's "SOI blocks onto INV crossbar groups")."""
    return math.prod(int(d) for d in shape[:-2])


def block_size_for(d: int, cap: int, align: int = 16) -> int:
    """Mesh-aligned SOI block size for a feature dimension ``d``.

    The paper sizes SOI blocks to fit INV crossbar *groups* ("we can
    always use the proper SOI matrix sizes to fulfill the limitation of
    INV crossbars", Sec. IV-A). The TPU analogue: size blocks so the
    (d) -> (nb, bs) blocking is *shard-local* on an ``align``-way mesh
    axis — i.e. bs divides the per-shard width d/align — which makes
    the factor layout, the blocked-gradient reshape and the
    preconditioning einsum all communication-free (EXPERIMENTS.md
    §Perf 1.4). Preference order:

      1. d <= cap: one whole block (reshape trivially local);
      2. largest bs dividing both d and d/align with bs >= 128;
      3. fallback: cap (pad semantics; only for dims not divisible by
         the mesh, e.g. MoE d_ff=1408 — noted per-arch).
    """
    if d <= cap:
        return d
    if d % align == 0:
        shard = d // align
        for bs in range(min(cap, shard), 127, -1):
            if shard % bs == 0 and d % bs == 0:
                return bs
    # no aligned size: prefer an exact divisor (no padding waste in the
    # inversions) before falling back to a padded cap-sized block
    for bs in range(min(cap, d), 127, -1):
        if d % bs == 0:
            return bs
    return cap


def pad_to_blocks(x: jax.Array, axis: int, bs: int) -> jax.Array:
    d = x.shape[axis]
    pad = n_blocks(d, bs) * bs - d
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@jax.named_scope("soi_gram")
def blocked_gram(a: jax.Array, cap: int) -> jax.Array:
    """Diagonal-block Gram of activations.

    ``a``: (..., T, d) tokens-by-features. Returns (..., nb, bs, bs)
    with bs = :func:`block_size_for`(d, cap) and block ``i`` =
    ``a_i^T a_i / T`` for the i-th feature slab (paper: ``A = a a^T``
    per diagonal block, Sec. VI-E).
    """
    t = a.shape[-2]
    bs = block_size_for(a.shape[-1], cap)
    a = pad_to_blocks(a, -1, bs)
    nb = a.shape[-1] // bs
    a = a.reshape(a.shape[:-1] + (nb, bs))
    gram = jnp.einsum("...tib,...tic->...ibc", a, a,
                      preferred_element_type=jnp.float32)
    return gram / jnp.asarray(t, jnp.float32)


def blocked_tokens(a: jax.Array, cap: int) -> jax.Array:
    """Blocked token columns of activations — the rank-k *square root*
    of :func:`blocked_gram`.

    ``a``: (..., T, d) -> (..., T, nb, bs), exactly the padded reshape
    :func:`blocked_gram` performs before its einsum. Keeping the raw
    columns (instead of contracting them on the spot) is what feeds the
    Sherman-Morrison-Woodbury incremental inverse refresh
    (``repro.solve.smw``): each step's Gram contribution is
    ``cols^T cols / T`` per block, rank ``T`` instead of a dense
    ``bs x bs`` rewrite — the PANTHER outer-product-update form.
    """
    bs = block_size_for(a.shape[-1], cap)
    a = pad_to_blocks(a, -1, bs)
    nb = a.shape[-1] // bs
    return a.reshape(a.shape[:-1] + (nb, bs))


@jax.named_scope("soi_gram")
def gram_from_tokens(bt: jax.Array) -> jax.Array:
    """(..., T, nb, bs) blocked tokens -> (..., nb, bs, bs) Gram.

    Same einsum (and therefore bitwise the same result) as
    :func:`blocked_gram` on the raw activations — the cols-collecting
    stats path uses this so the factor EMA stays on the standard
    trajectory while the columns ride along for SMW."""
    t = bt.shape[-3]
    gram = jnp.einsum("...tib,...tic->...ibc", bt, bt,
                      preferred_element_type=jnp.float32)
    return gram / jnp.asarray(t, jnp.float32)


def cols_from_tokens(bt: jax.Array) -> jax.Array:
    """(..., T, nb, bs) blocked tokens -> (..., nb, T, bs) per-block
    column factors ``V`` with Gram contribution ``V^T V / T``."""
    return jnp.moveaxis(bt, -3, -2)


def factor_shapes(spec: LinearSpec, cap: int) -> dict:
    """Zero-initialized factor pytree for one linear (per-side
    mesh-aligned block sizes)."""
    shapes = {}
    if spec.share_a_with is None:
        bi = block_size_for(spec.d_in, cap)
        shapes["A"] = spec.stack + (n_blocks(spec.d_in, bi), bi, bi)
    bo = block_size_for(spec.d_out, cap)
    shapes["G"] = spec.stack + (n_blocks(spec.d_out, bo), bo, bo)
    return shapes


def init_factors(specs: Mapping[str, LinearSpec], bs: int) -> dict:
    out = {}
    for name, spec in specs.items():
        out[name] = {k: jnp.zeros(v, jnp.float32)
                     for k, v in factor_shapes(spec, bs).items()}
    return out


def init_inverses(specs: Mapping[str, LinearSpec], bs: int) -> dict:
    """Inverses start as identity blocks => first steps are plain SGD."""
    out = {}
    for name, spec in specs.items():
        d = {}
        for k, shp in factor_shapes(spec, bs).items():
            eye = jnp.broadcast_to(
                jnp.eye(shp[-1], dtype=jnp.float32), shp)
            d[k + "_inv"] = eye
        out[name] = d
    return out


def two_sided_block_vmm(a_inv: jax.Array, gp: jax.Array,
                        g_inv: jax.Array, *,
                        precision: str = "fp32") -> jax.Array:
    """``A_inv[i] @ g[i, j] @ G_inv[j]`` on blocked tiles, contraction
    order pinned left-first. Both the per-leaf WU path (tiles batched
    over ``(*stack, nb_i, nb_o)``) and the pooled fused path (tiles
    batched over one flat pool dim) route through matmuls with exactly
    this association, which is what makes the two bitwise identical —
    a 3-operand einsum would leave the association to the contraction
    planner.

    ``precision`` routes both VMMs through
    :func:`core.quantize.lowp_einsum` — ``"fp32"`` lowers to exactly the
    historical einsums (bitwise identical), ``"hilo"``/``"int8"`` to
    the bf16-limb / integer-bit-sliced products. Per-leaf and pooled
    callers pass the same knob, so the parity contract holds at every
    precision.
    """
    tmp = quantize.lowp_einsum("...iab,...ibjc->...iajc", a_inv, gp,
                               precision=precision)
    return quantize.lowp_einsum("...iajc,...jcd->...iajd", tmp, g_inv,
                                precision=precision)


def gather_grad_tiles(g: jax.Array, stack: Tuple[int, ...], bi: int,
                      bo: int) -> jax.Array:
    """Blocked-gradient tiles in pool order.

    ``g``: (*stack, d_in, d_out) -> (prod(stack)*nb_i*nb_o, bi, bo),
    C-order over (stack..., i, j) — the tile enumeration the WU plan's
    ``a_src``/``g_src`` index arrays assume. Pad rows/cols are zero, so
    pooled trust-region dots over padded tiles equal the unpadded ones.
    """
    gp = pad_to_blocks(pad_to_blocks(g, -2, bi), -1, bo)
    nb_i, nb_o = gp.shape[-2] // bi, gp.shape[-1] // bo
    gp = gp.reshape(stack + (nb_i, bi, nb_o, bo))
    ls = len(stack)
    gp = gp.transpose(tuple(range(ls)) + (ls, ls + 2, ls + 1, ls + 3))
    return gp.reshape((-1, bi, bo))


def scatter_grad_tiles(tiles: jax.Array, stack: Tuple[int, ...],
                       nb_i: int, nb_o: int, d_in: int,
                       d_out: int) -> jax.Array:
    """Inverse of :func:`gather_grad_tiles`: (T, bi, bo) tiles back to
    the unpadded (*stack, d_in, d_out) gradient layout."""
    bi, bo = tiles.shape[-2], tiles.shape[-1]
    out = tiles.reshape(stack + (nb_i, nb_o, bi, bo))
    ls = len(stack)
    out = out.transpose(tuple(range(ls)) + (ls, ls + 2, ls + 1, ls + 3))
    out = out.reshape(stack + (nb_i * bi, nb_o * bo))
    return out[..., :d_in, :d_out]


def block_precondition(g: jax.Array, a_inv: jax.Array,
                       g_inv: jax.Array,
                       axes=("data", "model"), *,
                       precision: str = "fp32") -> jax.Array:
    """Apply ``blockdiag(A_inv) @ g @ blockdiag(G_inv)``.

    ``g``: (*stack, d_in, d_out); ``a_inv``: (*stack, nb_i, bi, bi);
    ``g_inv``: (*stack, nb_o, bo, bo) — per-side block sizes read from
    the inverse shapes (mesh-aligned, :func:`block_size_for`).

    Sharding: with aligned block sizes the (d)->(nb, bs) blockings are
    shard-local — the gradient's (data, model) layout maps exactly onto
    (nb_i/'data', nb_o/'model') — and the factor layout puts A blocks
    on 'data', G blocks on 'model' (dist/sharding.kfac_sharding), so
    both contractions of the einsum are communication-free: the TPU
    image of the paper's "each SOI block on its own INV crossbar
    group". Hints pin that layout (EXPERIMENTS.md §Perf 1.4).
    """
    ain, gout = axes[-2:]
    bi = a_inv.shape[-1]
    bo = g_inv.shape[-1]
    d_in, d_out = g.shape[-2], g.shape[-1]
    stack = g.shape[:-2]
    if len(axes) > 2:                   # explicit stack axes (MoE: E)
        ns = tuple(axes[:-2])[-len(stack):] if stack else ()
        ns = (None,) * (len(stack) - len(ns)) + ns
    else:
        ns = (None,) * len(stack)
    gp = pad_to_blocks(pad_to_blocks(g, -2, bi), -1, bo)
    nb_i, nb_o = gp.shape[-2] // bi, gp.shape[-1] // bo
    gp = gp.reshape(stack + (nb_i, bi, nb_o, bo))
    gp = shard_hint(gp, *ns, ain, None, gout, None)
    out = two_sided_block_vmm(a_inv, gp, g_inv, precision=precision)
    out = shard_hint(out, *ns, ain, None, gout, None)
    out = out.reshape(stack + (nb_i * bi, nb_o * bo))
    out = shard_hint(out, *ns, ain, gout)
    return out[..., :d_in, :d_out]


def tikhonov_damping(f: jax.Array, rel: float) -> jax.Array:
    """Per-block Tikhonov level: ``rel * tr(block)/bs`` (paper Sec. III-A:
    "Tikhonov regularization ... largely reduces the condition number").
    A small absolute floor keeps never-touched blocks invertible."""
    bs = f.shape[-1]
    tr = jnp.trace(f, axis1=-2, axis2=-1) / bs
    return rel * tr + 1e-8
