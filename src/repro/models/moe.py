"""Mixture-of-Experts FFN: capacity-based dispatch, two datapaths.

* **Fast path** (training/serving forward, no stats collection):
  ``shard_map`` expert parallelism. Experts are sharded over the
  ``model`` axis; every (data, model) device routes *its own* token
  shard, keeps only the (token, k) pairs bound for its local experts,
  runs them through local dispatch buffers, and the per-token partial
  outputs are summed with one ``psum`` over ``model``. Communication
  per layer = one D-width all-gather of the inputs (shared with the
  FFN anyway) + one activation-sized all-reduce — versus the GSPMD
  partitioning of the scatter/gather formulation, which replicated the
  dispatch buffers and all-reduced TBs per step (EXPERIMENTS.md §Perf
  pair 2).
* **Reference path** (K-FAC SU graph, smoke tests, no-mesh): the
  original global scatter dispatch — needed because the per-expert
  K-FAC factor taps/Grams are defined on the global (E, C, d) buffers
  (expert dim = factor-stack dim, DESIGN.md §4). The SU graph runs
  every ``stats_every`` steps on a token subsample, so its cost is
  amortized exactly like the paper's SOI updates.

Both paths implement the same math (top-k, capacity, drop) and are
cross-checked in tests/test_moe_paths.py.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist.api import BATCH_AXES, MODEL, fwd_psum, shard_hint
from repro.models.layers import Ctx, cast, dense_stacked, swiglu


def init_moe(cfg, key) -> Dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    s_in = d ** -0.5
    s_f = f ** -0.5
    return {
        "router": jax.random.normal(ks[0], (d, e), jnp.float32) * s_in,
        "wg": jax.random.normal(ks[1], (e, d, f), jnp.float32) * s_in,
        "wu": jax.random.normal(ks[2], (e, d, f), jnp.float32) * s_in,
        "wd": jax.random.normal(ks[3], (e, f, d), jnp.float32) * s_f,
    }


def capacity(cfg, n_tokens: int) -> int:
    c = int(cfg.capacity_factor * n_tokens * cfg.top_k / cfg.n_experts)
    return max(8, -(-c // 8) * 8)


def _routing(cfg, router, xf, dt):
    """Shared router math: returns (gate (nt,K), eid (nt,K))."""
    logits = jax.lax.dot_general(
        xf, cast(router, dt), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eid = jax.lax.top_k(probs, cfg.top_k)
    gate = gate / (jnp.sum(gate, -1, keepdims=True) + 1e-9)
    return gate, eid


def _local_moe(cfg, xf, router, wg, wu, wd, *, c_loc: int):
    """Per-device body of the shard_map fast path.

    ``xf``: (nt_loc, D) this data-shard's tokens (full D).
    ``wg/wu/wd``: (E_loc, ...) this model-shard's experts.
    ``c_loc``: per-device share of each expert's global capacity.
    Every op below is local; the closing psum sums expert partials.
    """
    dt = xf.dtype
    nt_loc, D = xf.shape
    e_loc = wg.shape[0]
    K = cfg.top_k
    gate, eid = _routing(cfg, router, xf, dt)          # global ids

    e0 = jax.lax.axis_index(MODEL) * e_loc
    lid = eid - e0                                     # local ids
    mine = (lid >= 0) & (lid < e_loc)

    flat_lid = jnp.where(mine, lid, e_loc).reshape(-1)  # e_loc = drop row
    onehot = jax.nn.one_hot(flat_lid, e_loc + 1, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1
    pos = jnp.take_along_axis(pos, flat_lid[:, None], axis=1)[:, 0]
    keep = mine.reshape(-1) & (pos < c_loc)
    safe_pos = jnp.where(keep, pos, c_loc)

    tok = jnp.repeat(jnp.arange(nt_loc), K)
    buf = jnp.zeros((e_loc, c_loc + 1, D), dt)
    buf = buf.at[jnp.clip(flat_lid, 0, e_loc - 1), safe_pos].add(
        xf[tok] * keep[:, None].astype(dt), mode="drop")
    buf = buf[:, :c_loc]

    g = jnp.einsum("ecd,edf->ecf", buf, cast(wg, dt),
                   preferred_element_type=jnp.float32).astype(dt)
    u = jnp.einsum("ecd,edf->ecf", buf, cast(wu, dt),
                   preferred_element_type=jnp.float32).astype(dt)
    y = jnp.einsum("ecf,efd->ecd", swiglu(g, u), cast(wd, dt),
                   preferred_element_type=jnp.float32).astype(dt)

    y = jnp.pad(y, ((0, 0), (0, 1), (0, 0)))
    gathered = y[jnp.clip(flat_lid, 0, e_loc - 1), safe_pos]
    w = (gate.reshape(-1) * keep.astype(jnp.float32)).astype(dt)
    out = jnp.zeros((nt_loc, D), dt).at[tok].add(gathered * w[:, None])
    # fwd_psum, not raw lax.psum: each shard contributes its local
    # experts' outputs with coefficient 1, so the backward is identity
    # on the replicated cotangent (raw psum would transpose to psum
    # under check_vma=False and scale grads by the axis size)
    return fwd_psum(out, MODEL)


def _moe_fast(cfg, p, xf, prefix):
    """shard_map EP dispatch (see module docstring)."""
    from jax import shard_map

    mesh = jax.sharding.get_abstract_mesh()
    axes = mesh.axis_names
    sizes = dict(mesh.shape)
    batch_axes = tuple(a for a in BATCH_AXES if a in axes)
    n_data = 1
    for a in batch_axes:
        n_data *= sizes[a]
    nt = xf.shape[0]
    # per-device share of each expert's global capacity (+8-rounded)
    c_loc = max(-(-capacity(cfg, nt) // n_data), 8)

    fn = shard_map(
        functools.partial(_local_moe, cfg, c_loc=c_loc),
        mesh=mesh,
        in_specs=(P(batch_axes if len(batch_axes) > 1
                    else (batch_axes[0] if batch_axes else None), None),
                  P(), P(MODEL, None, None), P(MODEL, None, None),
                  P(MODEL, None, None)),
        out_specs=P(batch_axes if len(batch_axes) > 1
                    else (batch_axes[0] if batch_axes else None), None),
        check_vma=False,
    )
    return fn(xf, p["router"], p["wg"], p["wu"], p["wd"])


def _use_fast_path(cfg, ctx, prefix) -> bool:
    from repro.dist.api import in_hint_guard

    if in_hint_guard():
        # already inside a manual (shard_map) region — the pipeline
        # stage program — where a nested shard_map over mesh axes is
        # illegal. EP still runs there: moe_ffn dispatches straight to
        # _local_moe over the pre-bound axes when the expert weights
        # arrive model-sliced (see moe_ffn); otherwise portable.
        return False
    if ctx is not None and ctx.collect:
        return False
    if ctx is not None and ctx.taps is not None and any(
            k.startswith(prefix) for k in ctx.taps):
        return False
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty or MODEL not in mesh.axis_names:
        return False
    nt_loc_ok = True      # shapes validated by shard_map itself
    return nt_loc_ok


@jax.named_scope("moe")
def moe_ffn(cfg, p: Dict, x: jax.Array, ctx: Optional[Ctx],
            prefix: str) -> jax.Array:
    """x: (B, T, D) -> (B, T, D). Top-k routing with capacity + drop."""
    B, T, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    nt = B * T
    C = capacity(cfg, nt)
    xf = x.reshape(nt, D)

    if _use_fast_path(cfg, ctx, prefix):
        out = _moe_fast(cfg, p, xf, prefix)
        return out.reshape(B, T, D)

    # --- EP-in-stage: inside the manual pipeline program the expert
    # weights arrive pre-sliced over the bound ``model`` axis, so the
    # shard_map fast-path body runs *directly* (its collectives —
    # axis_index + closing psum — are legal on pre-bound axes; only a
    # nested shard_map would not be). With data > 1 each shard routes
    # its own token slice against a per-device capacity share, exactly
    # as _moe_fast does from the outside. ---
    from repro.dist.api import bound_axes, bwd_psum_if_bound, \
        in_hint_guard
    if in_hint_guard() and p["wg"].shape[0] < E:
        ax = bound_axes()
        if ax.get(MODEL, 1) <= 1:
            raise ValueError(
                f"{prefix}: expert dim arrived sliced "
                f"({p['wg'].shape[0]} < {E}) but no bound '{MODEL}' "
                f"axis to dispatch over")
        n_data = 1
        for a in BATCH_AXES:
            n_data *= ax.get(a, 1)
        c_loc = max(-(-capacity(cfg, nt * n_data) // n_data), 8)
        # each shard's backward only sees its local experts' pull on
        # the inputs/router — reduce those partial cotangents (the
        # outer shard_map did this automatically for _moe_fast)
        xf = bwd_psum_if_bound(xf, MODEL)
        router = bwd_psum_if_bound(p["router"], MODEL)
        out = _local_moe(cfg, xf, router, p["wg"], p["wu"],
                         p["wd"], c_loc=c_loc)
        return out.reshape(B, T, D)

    # --- routing (router stays on the first-order path) ---
    logits = jax.lax.dot_general(
        xf, cast(p["router"], x.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eid = jax.lax.top_k(probs, K)            # (nt, K)
    gate = gate / (jnp.sum(gate, -1, keepdims=True) + 1e-9)

    # --- capacity assignment: position of each (token, k) in its expert
    # queue via one-hot cumsum (Switch-style) ---
    flat_eid = eid.reshape(-1)                     # (nt*K,)
    onehot = jax.nn.one_hot(flat_eid, E, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1           # (nt*K, E)
    pos = jnp.take_along_axis(pos, flat_eid[:, None], axis=1)[:, 0]
    keep = pos < C
    safe_pos = jnp.where(keep, pos, C)             # C = out-of-bounds slot

    tok = jnp.repeat(jnp.arange(nt), K)
    # --- dispatch: scatter tokens into (E, C, D) buffers ---
    buf = jnp.zeros((E, C, D), x.dtype)
    buf = buf.at[flat_eid, safe_pos].add(
        xf[tok] * keep[:, None].astype(x.dtype), mode="drop")
    buf = shard_hint(buf, MODEL, None, None)

    # --- expert FFN (einsum over the expert dim; EP via sharding) ---
    g = dense_stacked(buf, p["wg"], f"{prefix}/wg", ctx)
    u = dense_stacked(buf, p["wu"], f"{prefix}/wu", ctx,
                      collect_gram=False)
    h = swiglu(g, u)
    y = dense_stacked(h, p["wd"], f"{prefix}/wd", ctx)
    y = shard_hint(y, MODEL, None, None)

    # --- combine: gather expert outputs back to tokens ---
    gathered = y[flat_eid, safe_pos]               # (nt*K, D)
    w = (gate.reshape(-1) * keep.astype(jnp.float32)).astype(x.dtype)
    out = jnp.zeros((nt, D), x.dtype).at[tok].add(gathered * w[:, None])
    out = shard_hint(out, BATCH_AXES, MODEL)
    return out.reshape(B, T, D)
