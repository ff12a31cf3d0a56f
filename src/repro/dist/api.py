"""Mesh-axis vocabulary + the shard-hint API used by all model code.

Contract (consumed by models/*, core/soi, core/kfac, launch/steps):

* ``POD``/``DATA``/``MODEL`` — canonical mesh axis names;
  ``BATCH_AXES = (POD, DATA)`` is the batch-dim prefix (the ``pod``
  axis exists only on multi-pod meshes and is filtered automatically).
* :func:`shard_hint` — ``with_sharding_constraint`` that degrades to
  identity when no mesh is active and silently drops axes that are
  absent from the mesh or don't divide the dim. Model code can
  therefore hint unconditionally; smoke tests on 1 CPU device trace
  the exact same graphs.
* :func:`shard_like_params` — constrain a param-shaped tree (stacked
  gradients) onto the parameter layout, so the backward pass never
  materializes a replicated dW.
* :func:`path_key` — canonical '/'-joined pytree path; the key space
  shared by ``kfac_specs`` names, the factor dicts and the sharding
  rules.
* :func:`factor_axes` — the block-axes tuple ``soi.block_precondition``
  threads through its einsum hints, derived from the owning weight's
  partitioning (single source of truth: ``sharding._param_pspec``).
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import Any, Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

POD = "pod"
STAGE = "stage"
DATA = "data"
MODEL = "model"

#: Batch dims shard over the pure data-parallel axes (outer ``pod`` on
#: multi-pod meshes, inner ``data`` everywhere). The ``stage``
#: (pipeline) axis never carries batch: every stage sees every
#: microbatch, offset in time by the schedule (repro.pipeline).
BATCH_AXES: Tuple[str, ...] = (POD, DATA)

# Depth counter + bound-axes stack for :func:`hint_guard` regions
# (tracing is synchronous, so plain module state is race-free).
_HINTS_OFF = 0
_BOUND_AXES: list = []


@contextlib.contextmanager
def hint_guard(axes=None):
    """Disable :func:`shard_hint` inside the ``with`` body.

    Inside a ``shard_map`` region every mesh axis is *manual*, and a
    ``with_sharding_constraint`` naming those axes is illegal — but the
    model code hints unconditionally. The pipeline executor
    (``repro.pipeline.schedule``) traces the per-stage model body under
    this guard: there the shard_map program itself is the layout, so
    hints degrade to identity exactly like they do with no mesh active.

    ``axes`` optionally records the mesh-axis sizes bound by the
    enclosing shard_map (``{"stage": S, "data": dp, "model": mp}``).
    Model code queries them via :func:`bound_axes` to decide whether a
    manual collective over e.g. the ``model`` axis is legal — that is
    how tensor-parallel psums and EP dispatch run *inside* the stage
    program instead of falling back to portable paths.
    """
    global _HINTS_OFF
    _HINTS_OFF += 1
    _BOUND_AXES.append(dict(axes) if axes else {})
    try:
        yield
    finally:
        _HINTS_OFF -= 1
        _BOUND_AXES.pop()


def in_hint_guard() -> bool:
    """True while tracing inside a :func:`hint_guard` (manual shard_map)
    region — model code that would open nested shard_maps or emit
    sharding constraints must detour: either issue manual collectives
    over :func:`bound_axes` or take its portable path."""
    return bool(_HINTS_OFF)


def bound_axes() -> dict:
    """Axis sizes bound by the innermost :func:`hint_guard` region
    (empty outside a guard, or when the guard recorded none)."""
    return dict(_BOUND_AXES[-1]) if _BOUND_AXES else {}


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _fwd_psum(x, axis):
    return jax.lax.psum(x, axis)


def _fwd_psum_fwd(x, axis):
    return jax.lax.psum(x, axis), None


def _fwd_psum_bwd(axis, _, ct):
    # The summed output is replicated, so its cotangent is too; each
    # shard's partial contributes with coefficient 1 -> identity. (A raw
    # lax.psum would transpose to another psum under check_vma=False,
    # scaling the backward by the axis size.)
    return (ct,)


_fwd_psum.defvjp(_fwd_psum_fwd, _fwd_psum_bwd)


def fwd_psum(x: Any, axis: str) -> Any:
    """Unconditional ``lax.psum`` with identity backward, for code that
    always runs with ``axis`` bound (e.g. bodies of an explicit
    shard_map). See :func:`psum_if_bound` for the guarded variant."""
    return _fwd_psum(x, axis)


def psum_if_bound(x: Any, axis: str) -> Any:
    """``lax.psum(x, axis)`` iff tracing inside a :func:`hint_guard`
    region that bound ``axis`` with size > 1; identity otherwise —
    megatron's ``g`` operator (reduce forward, identity backward).

    This is the reduction seam for tensor-parallel partial sums in
    model code that runs both under GSPMD (where the compiler inserts
    the reduction from sharding constraints) and inside the manual
    pipeline stage program (where the model must reduce explicitly)."""
    if _HINTS_OFF and _BOUND_AXES and _BOUND_AXES[-1].get(axis, 1) > 1:
        return _fwd_psum(x, axis)
    return x


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _bwd_psum(x, axis):
    return x


def _bwd_psum_fwd(x, axis):
    del axis
    return x, None


def _bwd_psum_bwd(axis, _, ct):
    return (jax.lax.psum(ct, axis),)


_bwd_psum.defvjp(_bwd_psum_fwd, _bwd_psum_bwd)


def bwd_psum_if_bound(x: Any, axis: str) -> Any:
    """Identity in the forward whose COTANGENT is psummed over ``axis``
    — megatron's conjugate ``f`` operator — active only inside a
    :func:`hint_guard` region that bound ``axis`` with size > 1.

    Insert where a replicated activation fans into model-sliced weights
    (column-parallel q/k/v or gate/up projections): each shard's
    backward produces only its slice's contribution to the input
    cotangent, and this operator reduces those partials back to the
    true gradient before they reach the shared upstream graph."""
    if _HINTS_OFF and _BOUND_AXES and _BOUND_AXES[-1].get(axis, 1) > 1:
        return _bwd_psum(x, axis)
    return x


def _norm_entry(entry) -> Tuple[str, ...]:
    if entry is None:
        return ()
    if isinstance(entry, str):
        return (entry,)
    return tuple(entry)


def active_mesh():
    """The mesh in scope (``jax.set_mesh``), or None: the single place
    :func:`shard_hint` and :func:`shard_like_params` consult."""
    m = jax.sharding.get_abstract_mesh()
    if m is None or m.empty:
        return None
    return m


def clean_spec(spec, shape, mesh) -> P:
    """A PartitionSpec valid on ``mesh`` for an array of ``shape``.

    Per dim: keep only axis names present in the mesh, then drop axes
    (right-to-left) until the dim is divisible by the remaining axis
    product. Non-divisible dims therefore degrade to replication
    instead of crashing — any arch shards on any mesh."""
    sizes = dict(mesh.shape)
    out = []
    for dim, entry in zip(shape, spec):
        names = tuple(a for a in _norm_entry(entry) if a in sizes)
        n = math.prod(sizes[a] for a in names)
        while names and dim % n:
            n //= sizes[names[-1]]
            names = names[:-1]
        if not names:
            out.append(None)
        elif len(names) == 1:
            out.append(names[0])
        else:
            out.append(names)
    return P(*out)


def shard_hint(x: Any, *axes) -> Any:
    """Hint ``x``'s layout: one entry per leading dim (None | axis name |
    tuple of axis names). Identity when no mesh is active or inside a
    :func:`hint_guard` (manual shard_map) region."""
    if _HINTS_OFF:
        return x
    mesh = active_mesh()
    if mesh is None or not axes or not hasattr(x, "ndim"):
        return x
    spec = clean_spec(axes[: x.ndim], x.shape, mesh)
    if all(e is None for e in spec):
        return x
    if isinstance(mesh, jax.sharding.Mesh):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, spec))
    return jax.lax.with_sharding_constraint(x, spec)


def mesh_axes(mesh) -> Tuple[str, ...]:
    """Every axis name of ``mesh``, outer-to-inner — the combined-axis
    tuple the block-parallel solver shards its device-major block pool
    over (and all-gathers the inverse shards back across)."""
    return tuple(mesh.axis_names)


def mesh_ndev(mesh) -> int:
    """Total device count of ``mesh`` (``Mesh.size``; the prod fallback
    covers abstract-mesh stand-ins that only expose ``.shape``)."""
    size = getattr(mesh, "size", None)
    if size is not None:
        return int(size)
    return math.prod(dict(mesh.shape).values())


def path_key(path) -> str:
    """Canonical string for a jax pytree key path: ``a/b/0/c``."""
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def shard_like_params(tree: Any) -> Any:
    """Constrain a param-shaped tree (e.g. stacked dW from value_and_grad)
    onto the parameter sharding rules. No-op without an active mesh."""
    if active_mesh() is None:
        return tree
    from repro.dist.sharding import _param_pspec

    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for pth, leaf in flat:
        out.append(shard_hint(leaf, *_param_pspec(path_key(pth),
                                                  leaf.ndim)))
    return jax.tree_util.tree_unflatten(treedef, out)


def factor_axes(name: str) -> Tuple[Optional[str], ...]:
    """Block-axes for ``soi.block_precondition`` on the factored linear
    ``name``: ``(*stack_axes, a_block_axis, g_block_axis)``.

    Derived from the owning weight's partition spec so the gradient's
    (d_in, d_out) layout maps exactly onto (A-blocks, G-blocks) — both
    einsum contractions stay communication-free. MoE weights carry the
    expert dim on ``model`` as a stack axis."""
    from repro.dist.sharding import _param_pspec

    if "moe/" in name:
        return tuple(_param_pspec(name, 4))[1:]
    return tuple(_param_pspec(name, 3))[-2:]
