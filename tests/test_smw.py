"""Incremental SOI (repro.solve.smw / pdiv / kernels.smw_update).

Pins the tentpole contracts: the Woodbury update honoring the EMA decay
exactly; a long simulated trajectory where the SMW-updated inverse
tracks the fully re-inverted one within the drift budget (hypothesis
property, satellite); the rank-k Pallas kernel bitwise against its
ref.py oracle; the cols-collection path producing bitwise-identical
factor Grams; the divide-and-conquer inversion against plain linalg;
and the host-side drift gate (SMWRefresher) including its one-step
readback lag.
"""

from typing import Any, NamedTuple

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax
import jax.numpy as jnp

from repro.core import kfac, soi
from repro.core.kfac import KFACConfig
from repro.solve import SMWConfig, pdiv_invert, probe_drift, smw_refresh
from repro.solve.async_refresh import SMWRefresher
from repro.solve.smw import _subsample_cols, smw_update_flat


def _spd(r, shape, samples=2):
    n = shape[-1]
    a = r.standard_normal(shape[:-1] + (samples * n,)).astype(np.float32)
    return jnp.asarray(
        np.einsum("...ij,...kj->...ik", a, a) / (samples * n))


# ---------------------------------------------------------------------------
# the Woodbury identity itself
# ---------------------------------------------------------------------------

def test_smw_update_is_exact_woodbury():
    """inv(d*D + c*V^T V) from inv(D): exact up to fp32 (the decay is
    honored by scaling the inverse, not re-approximated)."""
    r = np.random.default_rng(0)
    n, bs, k = 3, 16, 4
    d_mat = _spd(r, (n, bs, bs)) + 0.05 * jnp.eye(bs)
    m0 = jnp.linalg.inv(d_mat)
    v = jnp.asarray(r.standard_normal((n, k, bs)).astype(np.float32))
    decay, c = 0.95, 0.05 * 0.7
    upd = smw_update_flat(m0, v, decay, c)
    truth = jnp.linalg.inv(
        decay * d_mat + c * jnp.einsum("nkb,nkc->nbc", v, v))
    np.testing.assert_allclose(np.asarray(upd), np.asarray(truth),
                               atol=2e-5, rtol=1e-4)


def test_subsample_cols_strides_and_rescales():
    r = np.random.default_rng(1)
    v = jnp.asarray(r.standard_normal((2, 8, 4)).astype(np.float32))
    assert _subsample_cols(v, 8) is v
    sub = _subsample_cols(v, 4)
    assert sub.shape == (2, 4, 4)
    np.testing.assert_allclose(
        np.asarray(sub), np.asarray(v[:, ::2, :]) * np.sqrt(2.0),
        rtol=1e-6)


# ---------------------------------------------------------------------------
# satellite: SMW tracks the fully re-inverted path over >=100 steps
# ---------------------------------------------------------------------------

@settings(max_examples=3, deadline=None)
@given(seed=st.integers(0, 10_000), k=st.integers(2, 6),
       decay=st.sampled_from([0.9, 0.95]))
def test_smw_tracks_full_reinversion_long_run(seed, k, decay):
    """>=100 simulated EMA steps: drift-gated SMW stays within budget
    of the fully re-inverted inverse, and the gate does not degenerate
    into falling back every step."""
    bs, steps, budget = 16, 110, 0.05
    r = np.random.default_rng(seed)
    cfg = KFACConfig(inv_method="exact", ema_decay=decay)
    f = _spd(r, (1, bs, bs))

    def full_inv(f):
        lam = soi.tikhonov_damping(f, cfg.damping)
        return jnp.linalg.inv(f + lam[:, None, None] * jnp.eye(bs))

    inv = full_inv(f)
    n_fallbacks = 0
    for t in range(steps):
        v = jnp.asarray(
            r.standard_normal((1, k, bs)).astype(np.float32)
            / np.sqrt(k, dtype=np.float32))
        f = decay * f + (1 - decay) * jnp.einsum("nkb,nkc->nbc", v, v)
        inv = smw_update_flat(inv, v, decay, 1.0 - decay)
        drift = float(probe_drift({"x": {"G": f}},
                                  {"x": {"G_inv": inv}}, cfg))
        if not (drift <= budget):
            inv = full_inv(f)
            n_fallbacks += 1
    # tracked inverse within (a small multiple of) the budget of truth
    truth = full_inv(f)
    rel = float(jnp.max(jnp.abs(inv - truth))
                / jnp.max(jnp.abs(truth)))
    assert rel <= 10 * budget, (rel, n_fallbacks)
    assert n_fallbacks < steps, "gate fell back every step"


# ---------------------------------------------------------------------------
# Pallas kernel vs ref.py oracle
# ---------------------------------------------------------------------------

def test_smw_kernel_bitwise_vs_oracle():
    from repro.kernels import ops, ref

    r = np.random.default_rng(2)
    n, bs, k = 3, 40, 5         # deliberately unaligned -> padded
    inv = jnp.linalg.inv(_spd(r, (n, bs, bs)) + 0.05 * jnp.eye(bs))
    v = jnp.asarray(r.standard_normal((n, k, bs)).astype(np.float32))
    ker = ops.smw_update(inv, v, decay=0.95, cscale=0.05)
    orc = ref.smw_update_ref(inv, v, decay=0.95, cscale=0.05)
    assert ker.shape == (n, bs, bs)
    # the same padded two-pass pipeline and partial products, but the
    # interpreter and the oracle let XLA order each dot's fp32 sums
    # differently: agreement to reassociation error (~32 ulp of the
    # inverse's scale), not bitwise
    orc = np.asarray(orc)
    np.testing.assert_allclose(np.asarray(ker), orc, rtol=0,
                               atol=2.0 ** -18 * np.abs(orc).max())


def test_smw_kernel_close_to_fp32_path():
    from repro.kernels import ops, ref

    r = np.random.default_rng(3)
    n, bs, k = 2, 32, 4
    inv = jnp.linalg.inv(_spd(r, (n, bs, bs)) + 0.05 * jnp.eye(bs))
    v = jnp.asarray(r.standard_normal((n, k, bs)).astype(np.float32))
    ker = ops.smw_update(inv, v, decay=0.95, cscale=0.05)
    exact = ref.exact_smw_update(inv, v, decay=0.95, cscale=0.05)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(exact),
                               atol=5e-3, rtol=5e-3)
    jnp_path = smw_update_flat(inv, v, 0.95, 0.05)
    np.testing.assert_allclose(np.asarray(jnp_path), np.asarray(exact),
                               atol=1e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# tree-level refresh semantics
# ---------------------------------------------------------------------------

def test_smw_refresh_tree_weights_and_skips():
    """A side uses w=1/k (token-mean Gram), G side w=1; leaves without
    cols keep their inverse bitwise untouched."""
    r = np.random.default_rng(4)
    bs, k = 16, 4
    cfg = KFACConfig(inv_method="exact")
    d = cfg.ema_decay
    fa, fg, fo = (_spd(r, (1, bs, bs)) for _ in range(3))
    inv = {
        "lin": {"A_inv": jnp.linalg.inv(fa + 0.05 * jnp.eye(bs)),
                "G_inv": jnp.linalg.inv(fg + 0.05 * jnp.eye(bs))},
        "other": {"G_inv": jnp.linalg.inv(fo + 0.05 * jnp.eye(bs))},
    }
    va = jnp.asarray(r.standard_normal((1, k, bs)).astype(np.float32))
    vg = jnp.asarray(r.standard_normal((1, k, bs)).astype(np.float32))
    factors = {
        "lin": {"A": d * fa + (1 - d) / k
                * jnp.einsum("nkb,nkc->nbc", va, va),
                "G": d * fg + (1 - d)
                * jnp.einsum("nkb,nkc->nbc", vg, vg)},
        "other": {"G": fo},
    }
    cols = {"lin": {"A": va, "G": vg}}
    new_inv, drift = smw_refresh(inv, factors, cols, cfg, SMWConfig())
    assert float(drift) >= 0 and np.isfinite(float(drift))
    np.testing.assert_array_equal(
        np.asarray(new_inv["other"]["G_inv"]),
        np.asarray(inv["other"]["G_inv"]))
    np.testing.assert_array_equal(
        np.asarray(new_inv["lin"]["A_inv"]),
        np.asarray(smw_update_flat(inv["lin"]["A_inv"], va, d,
                                   (1 - d) / k)))
    np.testing.assert_array_equal(
        np.asarray(new_inv["lin"]["G_inv"]),
        np.asarray(smw_update_flat(inv["lin"]["G_inv"], vg, d,
                                   1.0 - d)))


# ---------------------------------------------------------------------------
# rank-k stats: cols path keeps the factor EMA trajectory bitwise
# ---------------------------------------------------------------------------

def _cols_model():
    """The tiny MLP of test_kfac.py, honoring the collect sentinel the
    way models.layers does: "cols" stores blocked tokens, truthy stores
    the blocked Gram."""
    from repro.core.soi import LinearSpec

    specs = {"w1": LinearSpec(d_in=6, d_out=8),
             "w2": LinearSpec(d_in=8, d_out=4)}

    def make_loss(collect):
        def loss_with_taps(params, taps, batch):
            x, y = batch
            acts = {}

            def store(name, a):
                acts[name] = (soi.blocked_tokens(a, 8)
                              if collect == "cols"
                              else soi.blocked_gram(a, 8))

            store("w1", x)
            h = jax.nn.relu(x @ params["w1"] + taps["w1"])
            store("w2", h)
            out = h @ params["w2"] + taps["w2"]
            loss = 0.5 * jnp.mean(jnp.sum((out - y) ** 2, -1))
            return loss, acts

        return loss_with_taps

    return specs, make_loss


def test_stats_rank_k_grams_bitwise_vs_stats_grams():
    specs, make_loss = _cols_model()
    r = np.random.default_rng(5)
    T = 16
    params = {"w1": jnp.asarray(r.standard_normal((6, 8)), jnp.float32),
              "w2": jnp.asarray(r.standard_normal((8, 4)), jnp.float32)}
    batch = (jnp.asarray(r.standard_normal((T, 6)), jnp.float32),
             jnp.asarray(r.standard_normal((T, 4)), jnp.float32))
    taps = {"w1": jnp.zeros((T, 8)), "w2": jnp.zeros((T, 4))}

    a_ref, g_ref, loss_ref, _ = kfac.stats_grams(
        make_loss(True), params, taps, batch, specs, bs=8)
    a_rk, g_rk, cols, loss_rk = kfac.stats_rank_k(
        make_loss("cols"), params, taps, batch, specs, bs=8)

    assert float(loss_ref) == float(loss_rk)
    for name in specs:
        np.testing.assert_array_equal(np.asarray(a_ref[name]),
                                      np.asarray(a_rk[name]))
        np.testing.assert_array_equal(np.asarray(g_ref[name]),
                                      np.asarray(g_rk[name]))
        # cols really are the rank-k factors of the same contribution
        a = cols[name]["A"]
        assert a.shape[-2] == T
        np.testing.assert_allclose(
            np.asarray(jnp.einsum("...kb,...kc->...bc", a, a) / T),
            np.asarray(a_rk[name]), atol=1e-5, rtol=1e-5)
        g = cols[name]["G"]
        assert g.shape[-2] == T
        np.testing.assert_allclose(
            np.asarray(jnp.einsum("...kb,...kc->...bc", g, g)),
            np.asarray(g_rk[name]), atol=1e-5, rtol=1e-4)


def test_make_smw_step_runs_on_smoke_model():
    """End-to-end through the real model: the collect="cols" sentinel
    flows to layers.dense/dense_stacked, and one fused program updates
    factors AND inverses with a finite drift scalar."""
    from repro.configs import get_smoke_config
    from repro.core import kfac as kfac_mod
    from repro.launch import steps as steps_mod
    from repro.launch.steps import TrainState

    cfg = get_smoke_config("qwen1.5-0.5b")
    kcfg = KFACConfig(block_size=32, stats_batch=2, stats_seq=16)
    mod = steps_mod.model_module(cfg)
    params = mod.init(cfg, jax.random.PRNGKey(0))
    specs = steps_mod.kfac_specs(cfg)
    state = TrainState(params, kfac_mod.init(params, specs, kcfg))
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32)}

    smw_step = jax.jit(steps_mod.make_smw_step(cfg, kcfg, SMWConfig()))
    state2, m = smw_step(state, batch)
    assert np.isfinite(float(m["smw_drift"]))
    assert np.isfinite(float(m["stats_loss"]))
    changed = jax.tree.map(
        lambda a, b: bool((np.asarray(a) != np.asarray(b)).any()),
        state.kfac.inverses, state2.kfac.inverses)
    assert any(jax.tree.leaves(changed)), "no inverse was updated"


# ---------------------------------------------------------------------------
# pdiv: local correctness (multidevice parity lives in
# tests/test_dist_solve_multidev.py)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("depth", [1, 2])
def test_pdiv_local_matches_linalg(depth):
    r = np.random.default_rng(6)
    n = 32
    blk = _spd(r, (n, n))[()]
    lam = 0.05
    cfg = KFACConfig(inv_method="exact")
    out = pdiv_invert(blk, lam, cfg, depth=depth)
    truth = jnp.linalg.inv(blk + lam * jnp.eye(n))
    np.testing.assert_allclose(np.asarray(out), np.asarray(truth),
                               atol=1e-4, rtol=1e-3)


def test_pdiv_depth0_is_base_inverse():
    r = np.random.default_rng(7)
    n = 16
    blk = _spd(r, (n, n))[()]
    cfg = KFACConfig(inv_method="exact")
    out = pdiv_invert(blk, 0.05, cfg, depth=0)
    truth = jnp.linalg.inv(blk + 0.05 * jnp.eye(n))
    np.testing.assert_allclose(np.asarray(out), np.asarray(truth),
                               atol=1e-5, rtol=1e-4)


def test_pdiv_rejects_odd_size():
    cfg = KFACConfig(inv_method="exact")
    blk = jnp.eye(15)
    with pytest.raises(ValueError, match="even"):
        pdiv_invert(blk, 0.05, cfg, depth=1)


# ---------------------------------------------------------------------------
# the host-side gate
# ---------------------------------------------------------------------------

class _KState(NamedTuple):
    factors: Any
    inverses: Any


class _TState(NamedTuple):
    kfac: _KState


def test_smw_refresher_lagged_gate_and_seed():
    """Step 0 always falls back (seeds real inverses + compiles the
    donated program); a large drift dispatched at step N triggers the
    fallback at step N+1 (one-step readback lag); drift measured on
    replaced inverses is discarded."""
    drifts = iter([0.01, 99.0, 0.01, 0.01, 0.01])
    calls = []

    def smw_step(state, batch):
        return state, {"smw_drift": jnp.float32(next(drifts))}

    def refresh_into(factors, retired):
        calls.append(1)
        return {"x": {"G_inv": jnp.ones((1, 2, 2))}}

    ref = SMWRefresher(smw_step, refresh_into, drift_budget=0.05)
    state = _TState(_KState({"x": {"G": jnp.zeros((1, 2, 2))}},
                            {"x": {"G_inv": jnp.zeros((1, 2, 2))}}))
    state, m = ref.step(state, None)           # step 0: forced seed
    assert m["smw_fallback"] == 1.0 and len(calls) == 1
    state, m = ref.step(state, None)           # dispatches 99.0; the
    assert m["smw_fallback"] == 0.0            # gate has not seen it
    state, m = ref.step(state, None)           # lagged readback -> trip
    assert m["smw_fallback"] == 1.0 and len(calls) == 2
    assert ref.last_drift == 99.0
    state, m = ref.step(state, None)           # post-fallback drift was
    assert m["smw_fallback"] == 0.0            # discarded: no re-trip
    assert ref.n_fallbacks == 2 and ref.n_steps == 4

    ref.reset()                                # elastic recovery
    state, m = ref.step(state, None)
    assert m["smw_fallback"] == 1.0, "reset must force a fallback"
