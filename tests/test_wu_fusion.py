"""Pooled fused WU graph (kfac.apply_updates(wu_plan=...)): plan
invariants, bitwise parity with the legacy per-leaf path across dense /
MoE-stacked / shared-A / padded specs, the fused_precond kernel vs its
oracle, per-path optimizer-state slimming, and the fused INV→VMM
solver's local image. The forced-multi-device parity lives in
tests/test_wu_fusion_multidev.py."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.core import kfac
from repro.core.kfac import KFACConfig
from repro.core.soi import LinearSpec
from repro.dist.api import path_key
from repro.launch import steps as steps_mod
from repro.solve import make_wu_plan, refresh_and_precondition

KCFG = KFACConfig(block_size=16, ns_iters=6, taylor_terms=2,
                  refine_steps=1)

# dense + shared-A + stacked + padded (d % bs != 0) + MoE-style stack:
# every geometry the plan/pool machinery must handle
SPECS = {
    "w1": LinearSpec(d_in=32, d_out=16),
    "w2": LinearSpec(d_in=32, d_out=16, share_a_with="w1"),
    "stk/w": LinearSpec(d_in=16, d_out=20, stack=(3,)),      # padded
    "moe/wg": LinearSpec(d_in=16, d_out=16, stack=(2, 2)),
    "moe/wu": LinearSpec(d_in=16, d_out=16, stack=(2, 2),
                         share_a_with="moe/wg"),
}


def _params():
    return {
        "w1": jnp.zeros((32, 16)),
        "w2": jnp.zeros((32, 16)),
        "stk": {"w": jnp.zeros((3, 16, 20))},
        "moe": {"wg": jnp.zeros((2, 2, 16, 16)),
                "wu": jnp.zeros((2, 2, 16, 16))},
        "bias": jnp.zeros((7,)),                 # first-order path
    }


def _spd(r, shape):
    bs = shape[-1]
    a = r.standard_normal(shape[:-1] + (2 * bs,)).astype(np.float32)
    return jnp.asarray(np.einsum("...ij,...kj->...ik", a, a) / (2 * bs))


def _state(seed=0):
    r = np.random.default_rng(seed)
    params = _params()
    state = kfac.init(params, SPECS, KCFG)
    state = state._replace(
        factors=jax.tree.map(lambda x: _spd(r, x.shape), state.factors))
    state = jax.jit(lambda s: kfac.refresh_inverses(s, KCFG))(state)
    grads = jax.tree.map(
        lambda p: jnp.asarray(r.standard_normal(p.shape), jnp.float32),
        params)
    return params, grads, state


def _assert_tree_bitwise(a, b):
    fa = jax.tree_util.tree_flatten_with_path(a)[0]
    fb = {jax.tree_util.keystr(p): v for p, v in
          jax.tree_util.tree_flatten_with_path(b)[0]}
    assert len(fa) == len(fb)
    for p, v in fa:
        np.testing.assert_array_equal(
            np.asarray(v), np.asarray(fb[jax.tree_util.keystr(p)]),
            err_msg=jax.tree_util.keystr(p))


# ---------------------------------------------------------------------------
# plan invariants
# ---------------------------------------------------------------------------

def test_wu_plan_covers_every_tile_once():
    _, _, state = _state()
    for ndev in (1, 3, 4):
        wu = make_wu_plan(SPECS, state.factors, KCFG, ndev=ndev)
        # every factored leaf appears in exactly one tile group and one
        # stacked group, with the tile count its geometry implies
        tile_names = [l.name for g in wu.groups for l in g.leaves]
        stack_names = [m.name for s in wu.stacked for m in s.members]
        assert sorted(tile_names) == sorted(SPECS)
        assert sorted(stack_names) == sorted(SPECS)
        for g in wu.groups:
            n = g.n_tiles
            assert g.a_src.shape == g.g_src.shape == (n,)
            # tiles device-major: every tile exactly once, pads are -1
            for slots, back in ((g.slots, g.gather_back),
                                (g.g_slots, g.g_gather_back)):
                real = slots[slots >= 0]
                assert sorted(real.tolist()) == list(range(n))
                m = slots.shape[1]
                for t, pos in enumerate(back.tolist()):
                    assert slots[pos // m, pos % m] == t
        # a_src/g_src address blocks inside the embedded INV plan pools
        by_bs = {p.bs: sum(p.leaf_counts) for p in wu.inv_plan.groups}
        for g in wu.groups:
            assert g.a_src.max() < by_bs[g.bi]
            assert g.g_src.max() < by_bs[g.bo]


def test_wu_plan_from_abstract_shapes():
    _, _, state = _state()
    ab = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state.factors)
    pa = make_wu_plan(SPECS, ab, KCFG, ndev=4)
    pb = make_wu_plan(SPECS, state.factors, KCFG, ndev=4)
    for ga, gb in zip(pa.groups, pb.groups):
        np.testing.assert_array_equal(ga.a_src, gb.a_src)
        np.testing.assert_array_equal(ga.slots, gb.slots)


def test_wu_plan_pool_bytes_cap():
    _, _, state = _state()
    tiny = make_wu_plan(SPECS, state.factors, KCFG, ndev=1,
                        pool_bytes_cap=0)
    assert all(not s.pooled for s in tiny.stacked)
    big = make_wu_plan(SPECS, state.factors, KCFG, ndev=1)
    assert any(s.pooled for s in big.stacked)


def test_precondition_rejects_stale_plan():
    """A plan built for a narrower spec set must fail loudly instead
    of passing raw gradients through for the uncovered leaves."""
    params, grads, state = _state()
    narrow = {k: v for k, v in SPECS.items() if k != "w1"}
    # w2 shares w1's A, so drop it too to keep the narrow plan valid
    narrow.pop("w2")
    wu = make_wu_plan(narrow, state.factors, KCFG, ndev=1)
    with pytest.raises(ValueError, match="does not cover"):
        kfac.precondition(grads, state, SPECS, KCFG, wu_plan=wu)


def test_wu_plan_rejects_mismatched_inv_plan():
    from repro.solve import make_plan

    _, _, state = _state()
    inv = make_plan(state.factors, 2, KCFG)
    with pytest.raises(ValueError, match="devices"):
        make_wu_plan(SPECS, state.factors, KCFG, ndev=4, inv_plan=inv)


# ---------------------------------------------------------------------------
# bitwise parity: pooled fused vs legacy per-leaf
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ndev", [1, 4])
def test_precondition_pooled_bitwise(ndev):
    params, grads, state = _state()
    wu = make_wu_plan(SPECS, state.factors, KCFG, ndev=ndev)
    ref = jax.jit(
        lambda g, s: kfac.precondition(g, s, SPECS, KCFG))(grads, state)
    got = jax.jit(
        lambda g, s: kfac.precondition(g, s, SPECS, KCFG, wu_plan=wu))(
            grads, state)
    _assert_tree_bitwise(ref, got)


@pytest.mark.parametrize("pool_elementwise", [False, True])
def test_apply_updates_pooled_bitwise(pool_elementwise):
    """Params AND the full optimizer state (momentum / Adam moments /
    step) must match the per-leaf reference bit for bit — the clip
    scale nu folds the same per-leaf dots in the same order."""
    params, grads, state = _state()
    wu = make_wu_plan(SPECS, state.factors, KCFG, ndev=1)
    p_ref, s_ref = jax.jit(lambda p, g, s: kfac.apply_updates(
        p, g, s, SPECS, KCFG))(params, grads, state)
    p_got, s_got = jax.jit(lambda p, g, s: kfac.apply_updates(
        p, g, s, SPECS, KCFG, wu_plan=wu,
        pool_elementwise=pool_elementwise))(params, grads, state)
    _assert_tree_bitwise(p_ref, p_got)
    _assert_tree_bitwise(s_ref.momentum, s_got.momentum)
    _assert_tree_bitwise(s_ref.adam_mu, s_got.adam_mu)
    _assert_tree_bitwise(s_ref.adam_nu, s_got.adam_nu)
    assert int(s_got.step) == int(s_ref.step)


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "moonlight-16b-a3b"])
def test_train_step_fused_bitwise_on_arch(arch):
    """The launch-layer wiring: make_train_step(wu_plan=...) on real
    smoke archs (dense + MoE-stacked) is bitwise the legacy step."""
    cfg = get_smoke_config(arch)
    kcfg = KFACConfig(block_size=32, ns_iters=4, taylor_terms=2,
                      refine_steps=1, stats_batch=2, stats_seq=16)
    mod = steps_mod.model_module(cfg)
    specs = steps_mod.kfac_specs(cfg)
    params = mod.init(cfg, jax.random.PRNGKey(0))
    state = kfac.init(params, specs, kcfg)
    r = np.random.default_rng(0)
    state = state._replace(
        factors=jax.tree.map(lambda x: _spd(r, x.shape), state.factors))
    state = jax.jit(lambda s: kfac.refresh_inverses(s, kcfg))(state)
    tstate = steps_mod.TrainState(params, state)
    batch = {"tokens": jnp.asarray(
        r.integers(0, cfg.vocab, (2, 16)), jnp.int32)}

    wu = steps_mod.make_wu_plan_for(cfg, kcfg)
    s_ref, m_ref = jax.jit(
        steps_mod.make_train_step(cfg, kcfg))(tstate, batch)
    s_got, m_got = jax.jit(
        steps_mod.make_train_step(cfg, kcfg, wu_plan=wu))(tstate, batch)
    _assert_tree_bitwise(s_ref.params, s_got.params)
    _assert_tree_bitwise(s_ref.kfac.momentum, s_got.kfac.momentum)
    assert float(m_ref["loss"]) == float(m_got["loss"])


def test_fused_wu_local_refresh_and_precondition_bitwise():
    """solve.refresh_and_precondition without a mesh: the single-
    process image of the fused INV→VMM program matches replicated
    refresh + legacy precondition bitwise."""
    params, grads, state = _state()
    wu = make_wu_plan(SPECS, state.factors, KCFG, ndev=1)
    gbn = {path_key(p): g for p, g in
           jax.tree_util.tree_flatten_with_path(grads)[0]
           if path_key(p) in SPECS}
    inv, pre = jax.jit(lambda f, g: refresh_and_precondition(
        f, g, KCFG, wu))(state.factors, gbn)
    _assert_tree_bitwise(state.inverses, inv)
    ref = jax.jit(
        lambda g, s: kfac.precondition(g, s, SPECS, KCFG))(grads, state)
    ref_by = {path_key(p): v for p, v in
              jax.tree_util.tree_flatten_with_path(ref)[0]}
    for name in gbn:
        np.testing.assert_array_equal(
            np.asarray(pre[name]), np.asarray(ref_by[name]),
            err_msg=name)


# ---------------------------------------------------------------------------
# fused_precond Pallas kernel vs oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(5, 16, 8), (3, 128, 64),
                                   (2, 130, 200)])
def test_fused_precond_kernel_matches_oracle(shape):
    from repro.kernels import fused_precond
    from repro.kernels.ref import exact_two_sided, fused_precond_ref

    n, bi, bo = shape
    r = np.random.default_rng(0)
    a = jnp.asarray(r.standard_normal((n, bi, bi)), jnp.float32)
    g = jnp.asarray(r.standard_normal((n, bi, bo)), jnp.float32)
    gi = jnp.asarray(r.standard_normal((n, bo, bo)), jnp.float32)
    out, dots = fused_precond(a, g, gi)
    ref_out, ref_dots = fused_precond_ref(a, g, gi)
    # tiles: the identical hi/lo partial-product set, but the kernel
    # multiplies 128-padded tiles and the oracle the unpadded ones, so
    # XLA sums each dot in its own order: they agree to fp32
    # reassociation error (~32 ulp of the tile's scale), not bitwise
    ref_out = np.asarray(ref_out)
    np.testing.assert_allclose(np.asarray(out), ref_out, rtol=0,
                               atol=2.0 ** -18 * np.abs(ref_out).max())
    # in-pass dot: the kernel reduces over the padded tile (zero pads),
    # so association can differ from the oracle's unpadded reduce at
    # the float level on non-aligned shapes
    np.testing.assert_allclose(np.asarray(dots), np.asarray(ref_dots),
                               rtol=1e-4, atol=1e-2)
    # and the bit-sliced path tracks the exact fp32 product
    ex = np.asarray(exact_two_sided(a, g, gi))
    rel = np.max(np.abs(np.asarray(out) - ex)) / np.max(np.abs(ex))
    assert rel < 1e-4


def test_precondition_kernel_path_allclose():
    """precondition(use_kernel=True) routes the tile-indexed pools
    through the Pallas program (interpret mode here): allclose to the
    einsum path — not bitwise, the kernel's matmuls are hi/lo
    bit-sliced — across the same mixed specs."""
    params, grads, state = _state()
    wu = make_wu_plan(SPECS, state.factors, KCFG, ndev=1)
    ref = jax.jit(
        lambda g, s: kfac.precondition(g, s, SPECS, KCFG))(grads, state)
    got = kfac.precondition(grads, state, SPECS, KCFG, wu_plan=wu,
                            use_kernel=True)
    for (p, a), b in zip(jax.tree_util.tree_flatten_with_path(ref)[0],
                         jax.tree.leaves(got)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4,
            err_msg=jax.tree_util.keystr(p))


def test_fused_precond_dot_is_trust_region_mass():
    from repro.kernels import fused_precond

    r = np.random.default_rng(1)
    a = jnp.asarray(r.standard_normal((4, 16, 16)), jnp.float32)
    g = jnp.asarray(r.standard_normal((4, 16, 16)), jnp.float32)
    gi = jnp.asarray(r.standard_normal((4, 16, 16)), jnp.float32)
    out, dots = fused_precond(a, g, gi)
    want = np.asarray(jnp.sum(out * g, axis=(-2, -1)))
    np.testing.assert_allclose(np.asarray(dots), want, rtol=1e-5,
                               atol=1e-4)


# ---------------------------------------------------------------------------
# per-path optimizer-state slimming
# ---------------------------------------------------------------------------

def test_state_moments_allocated_per_path():
    params = _params()
    state = kfac.init(params, SPECS, KCFG)
    flat = {path_key(p): v for p, v in
            jax.tree_util.tree_flatten_with_path(state.momentum)[0]}
    mu = {path_key(p): v for p, v in
          jax.tree_util.tree_flatten_with_path(state.adam_mu)[0]}
    for name, p in jax.tree_util.tree_flatten_with_path(params)[0]:
        key = path_key(name)
        if key in SPECS:
            assert flat[key].shape == p.shape
            assert mu[key].shape == (0,)          # placeholder
        else:
            assert flat[key].shape == (0,)
            assert mu[key].shape == p.shape
    # treedef is preserved: state trees zip against params trees
    assert (jax.tree_util.tree_structure(state.momentum)
            == jax.tree_util.tree_structure(params))
    p_bytes = sum(np.asarray(x).nbytes for x in jax.tree.leaves(params))
    m_bytes = sum(
        np.asarray(x).nbytes
        for t in (state.momentum, state.adam_mu, state.adam_nu)
        for x in jax.tree.leaves(t))
    assert m_bytes < 3 * p_bytes


def test_state_slim_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint import store

    params, grads, state = _state()
    p2, s2 = kfac.apply_updates(params, grads, state, SPECS, KCFG)
    store.save(str(tmp_path), 1, s2)
    restored, _ = store.restore(str(tmp_path), s2)
    _assert_tree_bitwise(s2.momentum, restored.momentum)
    _assert_tree_bitwise(s2.adam_mu, restored.adam_mu)
