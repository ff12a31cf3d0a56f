"""Mixture-of-Experts FFN: capacity-based dispatch (two datapaths), and
the dropless expert-share layer.

The expert-share layer (:func:`share_ffn`, ``cfg.dropless``; DeepSeek-V3
/ Moonlight) is told which experts it holds (``cfg.expert_first`` and
``cfg.experts_held`` of ``cfg.n_experts``), routes every token over all
of them (:func:`route`), and computes its own experts' part of the
result: the (token, choice) pairs are sorted by held expert and run
through grouped matmuls (``kernels/grouped_matmul``) whose row groups
are the held experts' token counts. No capacity, no drop: the buffer is
sized for the worst case (every choice of every token held here), and
the rows no token fills are skipped. On one device the layer runs
without its exchange; a mesh whose ``model`` axis is larger than one, or
the pipeline stage program, refuses it (the exchange is not written).
Its K-FAC factors are per held expert over that expert's ragged token
group: ``A_e = sum_{t->e} x x^T / n_e`` (collected in the forward) and
``G_e = sum_{t->e} g g^T`` (the cotangent of a tap the size of the
factor, :func:`_gram_tap`).

The capacity layer (:func:`moe_ffn`):

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

from repro.core import soi
from repro.dist.api import (
    BATCH_AXES,
    MODEL,
    active_mesh,
    fwd_psum,
    in_hint_guard,
    shard_hint,
)
from repro.kernels import grouped_matmul
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


# ---------------------------------------------------------------------------
# Dropless expert-share layer
# ---------------------------------------------------------------------------

def init_share(cfg, key) -> Dict:
    """Router over all ``n_experts`` (and its correction bias, zero) and
    the held experts' weights; expert ``e``'s weights come from
    ``fold_in(key_experts, e)``, so a share holds what the whole layer
    holds of its experts."""
    d, f, e = cfg.d_model, cfg.d_expert_, cfg.n_experts
    kr, ke = jax.random.split(key)

    def expert(i):
        ks = jax.random.split(jax.random.fold_in(ke, i), 3)
        return (jax.random.normal(ks[0], (d, f), jnp.float32) * d ** -0.5,
                jax.random.normal(ks[1], (d, f), jnp.float32) * d ** -0.5,
                jax.random.normal(ks[2], (f, d), jnp.float32) * f ** -0.5)

    wg, wu, wd = jax.vmap(expert)(cfg.expert_first + jnp.arange(cfg.held))
    return {
        "router": jax.random.normal(kr, (d, e), jnp.float32) * d ** -0.5,
        "bias": jnp.zeros((e,), jnp.float32),
        "wg": wg, "wu": wu, "wd": wd,
    }


def route(cfg, p: Dict, xf: jax.Array):
    """(weights (N, K) f32, expert ids (N, K)) over all ``n_experts``,
    as DeepSeek-V3's ``noaux_tc`` with one group routes: float32
    logits, sigmoid scores; the top ``K`` of score + correction bias are
    chosen, their weights are the unbiased scores, normalized over the
    ``K`` and scaled by ``routed_scale``. The bias has no gradient."""
    logits = jnp.dot(xf.astype(jnp.float32),
                     p["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    choice = scores + jax.lax.stop_gradient(p["bias"])
    _, eid = jax.lax.top_k(choice, cfg.top_k)
    w = jnp.take_along_axis(scores, eid, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * cfg.routed_scale, eid


@jax.custom_vjp
def _permute(x, idx, inv):
    """``x[idx]`` for a permutation ``idx`` whose inverse is ``inv``; the
    backward gathers by ``inv`` (no scatter)."""
    return jnp.take(x, idx, axis=0)


def _permute_fwd(x, idx, inv):
    return jnp.take(x, idx, axis=0), (idx, inv)


def _permute_bwd(res, ct):
    idx, inv = res
    return jnp.take(ct, inv, axis=0), None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gram_tap(y, z, sizes, cap):
    """Identity on ``y``; the cotangent of ``z`` (a zero tap the shape of
    the held experts' G factor) is each expert's blocked Gram of ``y``'s
    cotangent over its rows: ``G_e = sum_{t->e} g g^T``."""
    del z, sizes
    return y


def _gram_tap_fwd(y, z, sizes, cap):
    del z
    return y, sizes


def _gram_tap_bwd(cap, sizes, ct):
    bs = soi.block_size_for(ct.shape[-1], cap)
    return ct, grouped_matmul.group_gram(ct, sizes, bs), None


_gram_tap.defvjp(_gram_tap_fwd, _gram_tap_bwd)


def _grouped_linear(x, w, sizes, name, ctx: Optional[Ctx],
                    collect_gram: bool = True):
    """Held experts' ``x @ w[e]`` on sorted rows, with the per-expert A
    Gram collected and the G tap applied (module doc)."""
    with jax.named_scope("moe_experts"):
        y = grouped_matmul.gmm(x, w, sizes)
    if ctx is None:
        return y
    if ctx.collect == "cols":
        raise NotImplementedError(
            f"{name}: the rank-k (SMW) statistics path has no per-expert "
            f"columns for ragged token groups")
    if ctx.collect and collect_gram:
        bs = soi.block_size_for(x.shape[-1], ctx.soi_block)
        n = jnp.maximum(sizes[:-1], 1).astype(jnp.float32)
        ctx.stats[name] = grouped_matmul.group_gram(x, sizes, bs) \
            / n[:, None, None, None]
    if ctx.taps is not None and name in ctx.taps:
        y = _gram_tap(y, ctx.taps[name], sizes, ctx.soi_block)
    return y


def _check_share_scope(prefix):
    if in_hint_guard():
        raise NotImplementedError(
            f"{prefix}: the dropless expert layer has no exchange inside "
            f"the pipeline stage program (its tokens would need the "
            f"dropless all-to-all, which is not written)")
    mesh = active_mesh()
    if mesh is not None and dict(mesh.shape).get(MODEL, 1) > 1:
        raise NotImplementedError(
            f"{prefix}: the dropless expert layer runs one device's share "
            f"without its exchange; a '{MODEL}' axis of "
            f"{dict(mesh.shape)[MODEL]} needs the dropless all-to-all, "
            f"which is not written")


def share_ffn(cfg, p: Dict, x: jax.Array, ctx: Optional[Ctx],
              prefix: str) -> jax.Array:
    """x: (B, T, D) -> (B, T, D), the held experts' part of the routed
    result (module doc). Under ``ctx.collect`` the held experts' token
    counts are kept as ``<prefix>/counts``."""
    _check_share_scope(prefix)
    B, T, D = x.shape
    K, H = cfg.top_k, cfg.held
    n = B * T * K
    m = -(-n // grouped_matmul.ROW_TILE) * grouped_matmul.ROW_TILE
    xf = x.reshape(B * T, D)
    with jax.named_scope("moe_route"):
        w, eid = route(cfg, p, xf)
        lid = eid - cfg.expert_first
        mine = (lid >= 0) & (lid < H)
        # held experts first, in order; then the choices held elsewhere
        key = jnp.where(mine, lid, H).reshape(-1)
        order = jnp.argsort(key, stable=True)
        inv = jnp.argsort(order)
        counts = jnp.bincount(key, length=H + 1).astype(jnp.int32)
        sizes = counts.at[H].add(m - n)
        buf = _permute(jnp.repeat(xf, K, axis=0), order, inv)
        buf = jnp.pad(buf, ((0, m - n), (0, 0)))
    g = _grouped_linear(buf, p["wg"], sizes, f"{prefix}/wg", ctx)
    u = _grouped_linear(buf, p["wu"], sizes, f"{prefix}/wu", ctx,
                        collect_gram=False)
    with jax.named_scope("moe_experts"):
        h = swiglu(g, u)
    y = _grouped_linear(h, p["wd"], sizes, f"{prefix}/wd", ctx)
    with jax.named_scope("moe_route"):
        y = _permute(y[:n], inv, order).reshape(B * T, K, D)
        wm = jnp.where(mine, w, 0.0)
        out = jnp.einsum("tkd,tk->td", y.astype(jnp.float32), wm)
    if ctx is not None and ctx.collect:
        ctx.stats[f"{prefix}/counts"] = counts[:H]
    return out.astype(x.dtype).reshape(B, T, D)
