"""K-FAC second-order optimizer with RePAST composed-precision inversion.

Paper mapping (RePAST Sec. II-A, V-A):
  FP/BP graphs  -> ordinary forward/backward inside ``train_step``.
  WU graph      -> :func:`precondition` + :func:`apply_updates`
                   (``dW = A^{-1} (dL/dW) G^{-1}``, Eqn. 3).
  SU graph      -> :func:`stats_grams` (factor accumulation, every
                   ``stats_every`` steps on a token subsample — the paper
                   updates SOI every 10 batches) and
                   :func:`refresh_inverses` (the paper's high-precision
                   matrix inversion, Sec. III, on every diagonal block).

The factor-gradient (``g = dL/dy``) capture uses the *tap* trick: models
add a zeros "tap" tensor to every factored linear's output; the gradient
w.r.t. the tap is exactly the per-token output gradient, from which the G
Gram is formed. This keeps the whole pipeline purely functional (works
under jit/scan/pjit) without graph rewriting.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import quantize, soi
from repro.core.precision_inv import composed_inverse
from repro.core.soi import LinearSpec
from repro.dist.api import factor_axes, path_key


@dataclasses.dataclass(frozen=True)
class KFACConfig:
    lr: float = 3e-2
    momentum: float = 0.9
    damping: float = 0.03           # relative Tikhonov (of mean block trace)
    ema_decay: float = 0.95         # factor EMA
    block_size: int = 1024          # paper's INV-crossbar group limit
    stats_every: int = 10           # SU-graph cadence (paper: 10 batches)
    inv_every: int = 10             # inverse refresh cadence
    stats_batch: int = 8            # SU subsample: sequences per pass
    stats_seq: int = 1024           # SU subsample: tokens per sequence
    kl_clip: float = 1.0            # trust-region scale clip
    # inversion method: "composed" = paper scheme (NS + Neumann + refine),
    # "composed_fast" = beyond-paper variant dropping the Neumann stage —
    # on the MXU the refinement against full-precision A subsumes Loop A
    # at equal accuracy (the analog hardware can't touch full A cheaply;
    # the MXU can — EXPERIMENTS.md §Perf 3.5), "exact" = linalg baseline
    inv_method: str = "composed"
    ns_iters: int = 20              # Newton-Schulz iters (INV primitive)
    taylor_terms: int = 4           # Loop A terms ("composed" path)
    refine_steps: int = 2           # Loop x analogue
    weight_decay: float = 0.0
    # WU-graph matmul precision: "fp32" (bitwise-historical default),
    # "hilo" (bf16-limb products), "int8" (24-bit codes in 8-bit
    # slices), or any "int<T>b<S>" ladder rung — parsed by
    # core.quantize.precision_kind, routed at soi.two_sided_block_vmm
    precision: str = "fp32"
    # first-order path (non-factored params): adam-style
    adam_b1: float = 0.9
    adam_b2: float = 0.999
    adam_eps: float = 1e-8


class KFACState(NamedTuple):
    step: jax.Array                 # int32 scalar
    factors: Any                    # name -> {"A": ..., "G": ...}
    inverses: Any                   # name -> {"A_inv": ..., "G_inv": ...}
    # Optimizer moments are allocated per update path: factored leaves
    # use heavy-ball momentum only, first-order leaves Adam's mu/nu
    # only. The unused side holds a zero-size placeholder so every
    # tree keeps the params treedef (checkpoint/sharding layouts are
    # structure-stable) without paying full-model memory three times.
    momentum: Any                   # like params on factored leaves
    adam_mu: Any                    # like params on first-order leaves
    adam_nu: Any


def _moment_placeholder() -> jax.Array:
    return jnp.zeros((0,), jnp.float32)


def init(params: Any, specs: Mapping[str, LinearSpec],
         cfg: KFACConfig) -> KFACState:
    def mom(path, p):
        return (jnp.zeros_like(p) if path_key(path) in specs
                else _moment_placeholder())

    def adam(path, p):
        return (_moment_placeholder() if path_key(path) in specs
                else jnp.zeros_like(p))

    return KFACState(
        step=jnp.zeros((), jnp.int32),
        factors=soi.init_factors(specs, cfg.block_size),
        inverses=soi.init_inverses(specs, cfg.block_size),
        momentum=jax.tree_util.tree_map_with_path(mom, params),
        adam_mu=jax.tree_util.tree_map_with_path(adam, params),
        adam_nu=jax.tree_util.tree_map_with_path(adam, params),
    )


# ---------------------------------------------------------------------------
# SU graph: factor statistics
# ---------------------------------------------------------------------------

def make_taps(specs: Mapping[str, LinearSpec], tokens: int) -> dict:
    """Zero tap tensors, one per factored linear: (*stack, tokens, d_out).

    For MoE linears the token dim is the per-expert capacity (the model's
    dispatch buffer feeds the tap)."""
    return {name: jnp.zeros(spec.stack + (tokens, spec.d_out), jnp.float32)
            for name, spec in specs.items()}


def _stats_pass(loss_with_taps, params, taps, batch):
    """One tapped fwd+bwd: ``(loss, acts, tap_grads)``."""
    def f(p, t):
        loss, acts = loss_with_taps(p, t, batch)
        return loss, acts

    (loss, acts), tap_grads = jax.value_and_grad(
        f, argnums=1, has_aux=True)(params, taps)
    return loss, acts, tap_grads


def stats_grams(
    loss_with_taps: Callable[..., Tuple[jax.Array, dict]],
    params: Any,
    taps: dict,
    batch: Any,
    specs: Mapping[str, LinearSpec],
    bs: int,
) -> Tuple[dict, dict, jax.Array, dict]:
    """Run one SU pass: returns (A_grams, G_grams, loss, extras), the
    extras being the collected entries that are no factored linear's
    (the expert layer's token counts).

    ``loss_with_taps(params, taps, batch) -> (loss, acts)`` where ``acts``
    maps each factored-linear name to its input activations
    (*stack, T, d_in) (or a precomputed blocked Gram, shape
    (*stack, nb, bs, bs)). A ``grouped`` linear's tap gradient is its G
    Gram already (``models/moe.share_ffn``).
    """
    loss, acts, tap_grads = _stats_pass(loss_with_taps, params, taps,
                                        batch)

    a_grams, g_grams = {}, {}
    for name, spec in specs.items():
        g = tap_grads[name]                        # (*stack, T, d_out)
        t = g.shape[-2]
        # Fisher convention: G = E_t[g g^T] * T (sum over tokens of the
        # batch-mean gradient outer products).
        g_grams[name] = g if spec.grouped else soi.blocked_gram(
            g, bs) * jnp.asarray(t, jnp.float32)
        if spec.share_a_with is None:
            a = acts[name]
            if a.ndim >= 2 and a.shape[-1] == a.shape[-2] and a.ndim == len(
                    spec.stack) + 3:
                a_grams[name] = a                  # already a blocked gram
            else:
                a_grams[name] = soi.blocked_gram(a, bs)
    return a_grams, g_grams, loss, {k: v for k, v in acts.items()
                                    if k not in specs}


def stats_rank_k(
    loss_with_taps: Callable[..., Tuple[jax.Array, dict]],
    params: Any,
    taps: dict,
    batch: Any,
    specs: Mapping[str, LinearSpec],
    bs: int,
) -> Tuple[dict, dict, dict, jax.Array]:
    """SU pass that additionally exposes the rank-k column factors:
    ``(A_grams, G_grams, cols, loss)``.

    The per-step Gram contribution of every factor block is a rank-k
    product ``V^T V * w`` with k = subsample tokens — the G side's ``V``
    is the tap gradient (already materialized for ``stats_grams``), the
    A side's is the blocked activation columns, which requires the model
    to have been called with ``collect="cols"`` (``acts[name]`` is then
    ``soi.blocked_tokens``, shape (*stack, T, nb, bs), instead of a
    precomputed Gram). ``cols[name][side]`` is (*stack, nb, k, bs);
    the weight convention (``repro.solve.smw`` relies on it) is
    ``w = 1/k`` for A (token-mean Gram) and ``w = 1`` for G (Fisher
    sum-over-tokens). The returned Grams are bitwise identical to
    :func:`stats_grams` on the same inputs, so the factor EMA trajectory
    does not depend on which stats path ran. Contract: with
    ``collect="cols"`` every collected A entry *is* blocked tokens
    (``models.layers`` honors the sentinel in every stats writer);
    a shape-sniff as in :func:`stats_grams` would be ambiguous here
    (tokens with nb == bs look square too).
    """
    loss, acts, tap_grads = _stats_pass(loss_with_taps, params, taps,
                                        batch)

    a_grams, g_grams, cols = {}, {}, {}
    for name, spec in specs.items():
        g = tap_grads[name]                        # (*stack, T, d_out)
        t = g.shape[-2]
        g_grams[name] = soi.blocked_gram(g, bs) * jnp.asarray(
            t, jnp.float32)
        entry = {"G": soi.cols_from_tokens(soi.blocked_tokens(g, bs))}
        if spec.share_a_with is None:
            a = acts[name]              # blocked tokens (*stack,T,nb,bs)
            a_grams[name] = soi.gram_from_tokens(a)
            entry["A"] = soi.cols_from_tokens(a)
        cols[name] = entry
    return a_grams, g_grams, cols, loss


def update_factors(state: KFACState, a_grams: dict, g_grams: dict,
                   cfg: KFACConfig) -> KFACState:
    """EMA the new Grams into the running factors."""
    d = cfg.ema_decay
    new_factors = {}
    for name, f in state.factors.items():
        nf = dict(f)
        if "A" in f and name in a_grams:
            nf["A"] = d * f["A"] + (1.0 - d) * a_grams[name]
        if name in g_grams:
            nf["G"] = d * f["G"] + (1.0 - d) * g_grams[name]
        new_factors[name] = nf
    return state._replace(factors=new_factors)


# ---------------------------------------------------------------------------
# Inverse refresh: the paper's high-precision INV on every diagonal block
# ---------------------------------------------------------------------------

def invert_blocks_flat(flat: jax.Array, lam: jax.Array,
                       cfg: KFACConfig) -> jax.Array:
    """Invert a flat batch of damped blocks: (N, bs, bs) with per-block
    damping (N,), via the configured method. This is the single
    per-block inversion primitive shared by the replicated path below
    and the block-parallel solver (``repro.solve.block_solver``) — one
    code path, so distributed and replicated refreshes agree bitwise."""
    lam = lam.reshape((-1, 1, 1))
    if cfg.inv_method == "exact":
        eye = jnp.eye(flat.shape[-1], dtype=flat.dtype)
        return jnp.linalg.inv(flat + lam * eye)
    taylor = 1 if cfg.inv_method == "composed_fast" else cfg.taylor_terms
    return jax.vmap(
        lambda a, l: composed_inverse(
            a, l[0, 0], ns_iters=cfg.ns_iters,
            taylor_terms=taylor,
            refine_steps=cfg.refine_steps))(flat, lam)


def _invert_blocks(f: jax.Array, cfg: KFACConfig) -> jax.Array:
    """Invert (..., bs, bs) damped blocks with the composed-precision
    scheme (all O(n^3) work in bf16 partial products — see
    ``core/precision_inv.composed_inverse``)."""
    lam = soi.tikhonov_damping(f, cfg.damping)
    shape = f.shape
    flat = f.reshape((-1,) + shape[-2:])
    return invert_blocks_flat(flat, lam.reshape(-1), cfg).reshape(shape)


def refresh_inverses(state: KFACState, cfg: KFACConfig, *,
                     plan=None) -> KFACState:
    """Replicated inverse refresh: every device inverts every block.

    This is the baseline SU/INV graph. Production meshes should prefer
    the block-parallel solver (``repro.solve.invert_factor_tree`` via
    ``launch/steps.make_inv_refresh``), where each device inverts only
    its plan-owned ~1/ndev share — the paper's INV-crossbar-group
    distribution — and optionally the async double-buffered refresh
    (``repro.solve.AsyncInverseRefresher``).

    ``plan`` (a ``repro.solve.Plan`` built once host-side) reuses the
    partitioner's pooled block layout instead of re-deriving the
    per-leaf blocking on every call, so a sync refresh and the SMW
    fallback refresh share one plan object (and one traced pooling)
    rather than rebuilding that work per call. Results are bitwise
    identical either way (``invert_blocks_flat`` is the shared
    primitive; tests pin the pooled/per-leaf parity)."""
    if plan is not None:
        from repro.solve.block_solver import invert_factor_tree

        return state._replace(inverses=invert_factor_tree(
            state.factors, cfg, plan=plan))
    new_inv = {}
    for name, f in state.factors.items():
        d = {}
        if "A" in f:
            d["A_inv"] = _invert_blocks(f["A"], cfg)
        if "G" in f:
            d["G_inv"] = _invert_blocks(f["G"], cfg)
        new_inv[name] = d
    return state._replace(inverses=new_inv)


# ---------------------------------------------------------------------------
# WU graph: preconditioning + parameter update
# ---------------------------------------------------------------------------

def inverse_pools(inverses: Any, inv_plan) -> dict:
    """Concatenate the inverse tree into per-``bs`` flat pools
    ``{bs: (M, bs, bs)}`` in the plan's pooled block order — the layout
    the WU plan's ``a_src``/``g_src`` index and the block-parallel
    solver distributes device-major. Feeds the tile-indexed kernel
    path of :func:`precondition_pooled`."""
    pools = {}
    for g in inv_plan.groups:
        parts = [inverses[name][side + "_inv"].reshape((-1, g.bs, g.bs))
                 for name, side in g.leaves]
        pools[g.bs] = parts[0] if len(parts) == 1 else \
            jnp.concatenate(parts)
    return pools


def precondition_pooled(grads_by_name: Mapping[str, jax.Array],
                        inverses: Any, wu_plan,
                        use_kernel: bool = False,
                        precision: str = "fp32") -> dict:
    """Pooled fused WU graph: one batched two-sided block VMM per
    stacked geometry group instead of one einsum per leaf — the TPU
    image of the paper's fused VMM⊕INV crossbar groups (Sec. V).

    The local pooling is *concat-stacked* (same-(nb_i, bi, nb_o, bo)
    leaves ride one einsum batched over the concatenated stack axis):
    pure concatenations and slices, no index gathers — on CPU XLA a
    per-tile gather lowers to serial ``call`` ops that cost more than
    the per-leaf loop saved (measured in benchmarks/wu_fusion.py).
    The tile-indexed device-major pools (``wu_plan.groups``) are the
    distributed layout, consumed by ``solve.fused_wu`` under shard_map
    and by the ``kernels.fused_precond`` Pallas kernel on TPU.

    Per-tile math is :func:`soi.two_sided_block_vmm` with the same
    left-first association as the per-leaf path, so outputs are bitwise
    identical to :func:`precondition` (tests pin this). Groups marked
    unpooled (single member, or gradient bytes above the plan's
    pooling cap — concat copies beat dispatch savings there) fall back
    to the per-leaf einsum inside the same program.

    ``use_kernel`` routes the tile-indexed pools (``wu_plan.groups``)
    through the ``kernels.fused_precond`` Pallas program instead — the
    TPU path, where both VMMs run back-to-back in VMEM with the
    trust-region dot accumulated in the same pass. Its hi/lo bit-
    sliced products are allclose (not bitwise) to the einsum path, so
    it is opt-in and excluded from the parity contract.

    ``precision`` (``repro.lowp``) routes every pooled and per-leaf
    VMM through ``quantize.lowp_einsum``; "fp32" stays bitwise-
    historical. The Pallas kernel *is* the hilo scheme, so
    ``use_kernel`` composes with "fp32"/"hilo" but not the integer-
    sliced modes.
    """
    if use_kernel:
        if quantize.precision_kind(precision) not in ("fp32", "hilo"):
            raise ValueError(
                f"use_kernel supports precision 'fp32'/'hilo' (the "
                f"fused_precond kernel is the hi/lo scheme), not "
                f"{precision!r}")
        return _precondition_pooled_kernel(grads_by_name, inverses,
                                           wu_plan)
    out = {}
    for grp in wu_plan.stacked:
        bi, bo = grp.bi, grp.bo
        if not grp.pooled:
            for m in grp.members:
                out[m.name] = soi.block_precondition(
                    grads_by_name[m.name],
                    inverses[m.a_owner]["A_inv"],
                    inverses[m.name]["G_inv"],
                    axes=factor_axes(m.name),
                    precision=precision)
            continue
        def rs(x, shape):            # reshape only when it moves
            return x if x.shape == shape else x.reshape(shape)

        gs, a_s, g_s = [], [], []
        for m in grp.members:
            gp = soi.pad_to_blocks(soi.pad_to_blocks(
                grads_by_name[m.name], -2, bi), -1, bo)
            gs.append(rs(gp, (m.n_stack, grp.nb_i, bi, grp.nb_o, bo)))
            a_s.append(rs(inverses[m.a_owner]["A_inv"],
                          (m.n_stack, grp.nb_i, bi, bi)))
            g_s.append(rs(inverses[m.name]["G_inv"],
                          (m.n_stack, grp.nb_o, bo, bo)))
        o = soi.two_sided_block_vmm(
            jnp.concatenate(a_s), jnp.concatenate(gs),
            jnp.concatenate(g_s), precision=precision)
        ofs = 0
        for m in grp.members:
            blk = rs(o[ofs:ofs + m.n_stack],
                     m.stack + (grp.nb_i * bi, grp.nb_o * bo))
            if blk.shape[-2:] != (m.d_in, m.d_out):
                blk = blk[..., :m.d_in, :m.d_out]
            out[m.name] = blk
            ofs += m.n_stack
    return out


def _precondition_pooled_kernel(grads_by_name, inverses, wu_plan):
    """Tile-indexed pools -> ``kernels.fused_precond``: one Pallas
    program per (bi, bo) pool, every tile's A/G inverse gathered from
    the per-``bs`` pools the INV solver lays out. The kernel also
    emits per-tile TR dots in the same pass; this wrapper discards
    them (the parity-bound dot in :func:`apply_updates` folds per-leaf
    terms in the legacy order)."""
    from repro.kernels import ops as kernel_ops

    pools = inverse_pools(inverses, wu_plan.inv_plan)
    out = {}
    for grp in wu_plan.groups:
        tiles = [soi.gather_grad_tiles(grads_by_name[l.name], l.stack,
                                       grp.bi, grp.bo)
                 for l in grp.leaves]
        g_pool = tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles)
        a_sel = pools[grp.bi][jnp.asarray(grp.a_src)]
        g_sel = pools[grp.bo][jnp.asarray(grp.g_src)]
        o, _dots = kernel_ops.fused_precond(a_sel, g_pool, g_sel)
        ofs = 0
        for l in grp.leaves:
            n = l.n_tiles
            out[l.name] = soi.scatter_grad_tiles(
                o[ofs:ofs + n], l.stack, l.nb_i, l.nb_o, l.d_in,
                l.d_out)
            ofs += n
    return out


def precondition(grads: Any, state: KFACState,
                 specs: Mapping[str, LinearSpec], cfg: KFACConfig,
                 wu_plan=None, use_kernel: bool = False) -> Any:
    """Apply ``A^{-1} g G^{-1}`` to every factored weight's gradient
    (paper Eqn. 3 / the WU dataflow graph). Non-factored params pass
    through unchanged (they take the first-order path in
    :func:`apply_updates`).

    ``wu_plan`` (a ``repro.solve.WUPlan``) switches to the pooled fused
    program; without it the legacy per-leaf loop runs (kept for parity
    tests and as the no-plan fallback)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(grads)
    if wu_plan is not None:
        grads_by_name = {path_key(p): g for p, g in leaves
                         if path_key(p) in specs}
        pooled = precondition_pooled(grads_by_name, state.inverses,
                                     wu_plan, use_kernel=use_kernel,
                                     precision=cfg.precision)
        missing = set(grads_by_name) - set(pooled)
        if missing:
            # a stale plan (built for a different spec set) would
            # otherwise pass raw gradients through for the uncovered
            # factored leaves — silent training degradation
            raise ValueError(
                f"wu_plan does not cover factored leaves "
                f"{sorted(missing)}; rebuild it with make_wu_plan for "
                f"the current specs/factors")
        out = [pooled.get(path_key(p), g) for p, g in leaves]
        return jax.tree_util.tree_unflatten(treedef, out)
    out = []
    for path, g in leaves:
        name = path_key(path)
        if name in specs:
            spec = specs[name]
            inv = state.inverses[name]
            a_name = spec.share_a_with or name
            a_inv = state.inverses[a_name]["A_inv"]
            out.append(soi.block_precondition(
                g, a_inv, inv["G_inv"], axes=factor_axes(name),
                precision=cfg.precision))
        else:
            out.append(g)
    return jax.tree_util.tree_unflatten(treedef, out)


def _pooled_chain(idx, leaves_by_slot, fn, n_out):
    """Run one elementwise update chain over many leaves at once.

    ``idx``: leaf positions participating; ``leaves_by_slot``: tuples of
    per-position input leaves (p, d, m, ...); ``fn(vec...) -> vecs``
    operates on flat fp32 vectors. Leaves are raveled and concatenated
    per dtype group, the chain runs once per group, and the results are
    split back — elementwise ops are position-independent, so every
    output leaf is bitwise what the per-leaf loop computes, in ~2
    fused chains instead of one per leaf. The concat/split costs ~4
    extra full passes over the moment memory, which on CPU XLA is
    slower than the per-leaf chains it replaces (benchmarks/wu_fusion
    measured 2-3x) — hence opt-in ``pool_elementwise``, for backends
    where kernel-launch count dominates (TPU).
    Returns ``n_out`` dicts mapping leaf position -> updated leaf.
    """
    outs = [dict() for _ in range(n_out)]
    by_dtype: dict = {}
    for k in idx:
        by_dtype.setdefault(
            jnp.asarray(leaves_by_slot[0][k]).dtype, []).append(k)
    for ks in by_dtype.values():
        vecs = [jnp.concatenate([jnp.ravel(ins[k]) for k in ks])
                if len(ks) > 1 else jnp.ravel(ins[ks[0]])
                for ins in leaves_by_slot]
        res = fn(*vecs)
        ofs = 0
        for k in ks:
            ref = leaves_by_slot[0][k]
            sz = ref.size
            for slot in range(n_out):
                outs[slot][k] = res[slot][ofs:ofs + sz].reshape(
                    ref.shape)
            ofs += sz
    return outs


def _apply_updates_pooled(leaves_p, treedef, leaves_pre, leaves_g,
                          leaves_m, leaves_mu, leaves_nu, names, nu,
                          stepf, step, state: KFACState,
                          cfg: KFACConfig) -> Tuple[Any, KFACState]:
    """Pooled elementwise tail of the fused WU program: one momentum
    chain over every factored leaf, one Adam chain over every
    first-order leaf (moment placeholders pass through untouched)."""
    n = len(leaves_p)
    fact = [k for k in range(n) if path_key(leaves_p[k][0]) in names]
    sfact = set(fact)
    adam = [k for k in range(n) if k not in sfact]
    ps = [p for _, p in leaves_p]

    new_p = list(ps)
    new_m = list(leaves_m)
    new_mu = list(leaves_mu)
    new_nu = list(leaves_nu)

    if fact:
        def mom_chain(p, d, m):
            m2 = cfg.momentum * m + d * nu
            upd = cfg.lr * m2 + cfg.lr * cfg.weight_decay * p
            return p - upd, m2

        got_p, got_m = _pooled_chain(
            fact, (ps, leaves_pre, leaves_m), mom_chain, 2)
        for k in fact:
            new_p[k] = got_p[k]
            new_m[k] = got_m[k]

    if adam:
        def adam_chain(p, g, mu, nvu):
            mu2 = cfg.adam_b1 * mu + (1 - cfg.adam_b1) * g
            nu2 = cfg.adam_b2 * nvu + (1 - cfg.adam_b2) * g * g
            mhat = mu2 / (1 - cfg.adam_b1 ** stepf)
            nhat = nu2 / (1 - cfg.adam_b2 ** stepf)
            p2 = p - cfg.lr * mhat / (jnp.sqrt(nhat) + cfg.adam_eps)
            return p2, mu2, nu2

        got_p, got_mu, got_nu = _pooled_chain(
            adam, (ps, leaves_g, leaves_mu, leaves_nu), adam_chain, 3)
        for k in adam:
            new_p[k] = got_p[k]
            new_mu[k] = got_mu[k]
            new_nu[k] = got_nu[k]

    params2 = jax.tree_util.tree_unflatten(treedef, new_p)
    state2 = state._replace(
        step=step,
        momentum=jax.tree_util.tree_unflatten(treedef, new_m),
        adam_mu=jax.tree_util.tree_unflatten(treedef, new_mu),
        adam_nu=jax.tree_util.tree_unflatten(treedef, new_nu),
    )
    return params2, state2


@jax.named_scope("wu")
def apply_updates(params: Any, grads: Any, state: KFACState,
                  specs: Mapping[str, LinearSpec],
                  cfg: KFACConfig, wu_plan=None,
                  pool_elementwise: bool = False
                  ) -> Tuple[Any, KFACState]:
    """Momentum + trust-region-clipped update.

    Factored params: preconditioned direction with heavy-ball momentum.
    Non-factored params (norms, embeddings, gates): Adam.

    With ``wu_plan`` (a ``repro.solve.WUPlan``) the preconditioning
    runs pooled-fused — batched VMM⊕INV programs over the plan's
    stacked geometry groups — bitwise identical to the per-leaf
    reference below. ``pool_elementwise`` additionally concatenates
    the momentum/Adam chains into one fused chain per update path
    (bitwise-identical too); it trades ~4 extra moment-memory passes
    for ~n_leaves fewer kernels, a win only where launch overhead
    dominates (TPU), so it is off by default."""
    pre = precondition(grads, state, specs, cfg, wu_plan=wu_plan)
    names = {name for name in specs}

    # KL/trust-region clip: scale the preconditioned step so that
    # sum(d * g) <= kl_clip (simplified from K-FAC's quadratic model).
    # Only factored leaves participate: on the Adam path ``pre is g``,
    # so including those leaves adds plain |g|^2 mass that inflates the
    # clip and spuriously shrinks ``nu`` for the preconditioned step
    # (the Adam update is scale-invariant in g and needs no clip).
    # Both WU paths fold the per-leaf dots in this exact order, so the
    # clip scale — and with it the whole update — stays bitwise equal.
    leaves_pre_p, _ = jax.tree_util.tree_flatten_with_path(pre)
    terms = [jnp.sum(d * g) for (path, d), g in zip(
        leaves_pre_p, jax.tree.leaves(grads))
        if path_key(path) in names]
    dot = sum(terms) if terms else jnp.zeros((), jnp.float32)
    nu = jnp.minimum(1.0, cfg.kl_clip / (cfg.lr * jnp.abs(dot) + 1e-12))

    step = state.step + 1
    stepf = step.astype(jnp.float32)

    flat_p = jax.tree_util.tree_flatten_with_path(params)
    leaves_p, treedef = flat_p
    leaves_pre = jax.tree.leaves(pre)
    leaves_g = jax.tree.leaves(grads)
    leaves_m = jax.tree.leaves(state.momentum)
    leaves_mu = jax.tree.leaves(state.adam_mu)
    leaves_nu = jax.tree.leaves(state.adam_nu)

    if wu_plan is not None and pool_elementwise:
        return _apply_updates_pooled(
            leaves_p, treedef, leaves_pre, leaves_g, leaves_m,
            leaves_mu, leaves_nu, names, nu, stepf, step, state, cfg)

    new_p, new_m, new_mu, new_nu = [], [], [], []
    for (path, p), d, g, m, mu, nvu in zip(
            leaves_p, leaves_pre, leaves_g, leaves_m, leaves_mu, leaves_nu):
        name = path_key(path)
        if name in names:
            m2 = cfg.momentum * m + d * nu
            upd = cfg.lr * m2 + cfg.lr * cfg.weight_decay * p
            new_p.append(p - upd)
            new_m.append(m2)
            new_mu.append(mu)
            new_nu.append(nvu)
        else:
            mu2 = cfg.adam_b1 * mu + (1 - cfg.adam_b1) * g
            nu2 = cfg.adam_b2 * nvu + (1 - cfg.adam_b2) * g * g
            mhat = mu2 / (1 - cfg.adam_b1 ** stepf)
            nhat = nu2 / (1 - cfg.adam_b2 ** stepf)
            new_p.append(p - cfg.lr * mhat / (jnp.sqrt(nhat) + cfg.adam_eps))
            new_m.append(m)
            new_mu.append(mu2)
            new_nu.append(nu2)

    params2 = jax.tree_util.tree_unflatten(treedef, new_p)
    state2 = state._replace(
        step=step,
        momentum=jax.tree_util.tree_unflatten(treedef, new_m),
        adam_mu=jax.tree_util.tree_unflatten(treedef, new_mu),
        adam_nu=jax.tree_util.tree_unflatten(treedef, new_nu),
    )
    return params2, state2
