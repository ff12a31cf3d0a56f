"""K-FAC correctness: factors match explicit E[aa^T]/E[gg^T] on a tiny
MLP; preconditioning solves the block system; end-to-end step beats SGD
on a quadratic; pimsim cycle/cost models match the paper's equations."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import kfac, soi
from repro.core.kfac import KFACConfig
from repro.core.soi import LinearSpec


# ---------------------------------------------------------------------------
# factor capture on a hand-checkable model
# ---------------------------------------------------------------------------

def _tiny_model():
    """y = relu(x W1) W2, MSE loss; one factored linear per layer."""
    specs = {
        "w1": LinearSpec(d_in=6, d_out=8),
        "w2": LinearSpec(d_in=8, d_out=4),
    }

    def loss_with_taps(params, taps, batch):
        x, y = batch
        acts = {}
        a1 = x
        acts["w1"] = soi.blocked_gram(a1, 8)
        h = a1 @ params["w1"] + taps["w1"]
        h = jax.nn.relu(h)
        acts["w2"] = soi.blocked_gram(h, 8)
        out = h @ params["w2"] + taps["w2"]
        loss = 0.5 * jnp.mean(jnp.sum((out - y) ** 2, -1))
        return loss, acts

    return specs, loss_with_taps


def test_stats_grams_match_manual():
    specs, loss_with_taps = _tiny_model()
    r = np.random.default_rng(0)
    T = 16
    params = {"w1": jnp.asarray(r.standard_normal((6, 8)), jnp.float32),
              "w2": jnp.asarray(r.standard_normal((8, 4)), jnp.float32)}
    x = jnp.asarray(r.standard_normal((T, 6)), jnp.float32)
    y = jnp.asarray(r.standard_normal((T, 4)), jnp.float32)
    taps = {"w1": jnp.zeros((T, 8)), "w2": jnp.zeros((T, 4))}

    a_grams, g_grams, loss, _ = kfac.stats_grams(
        loss_with_taps, params, taps, (x, y), specs, bs=8)

    # A factor: E[a a^T] per block (block-padded to bs=8; d_in=6 live)
    np.testing.assert_allclose(
        np.asarray(a_grams["w1"][0])[:6, :6],
        np.asarray(x.T @ x / T), rtol=1e-5)
    assert np.all(np.asarray(a_grams["w1"][0])[6:, :] == 0)

    # G factor: gradients w.r.t. layer outputs, computed by hand
    h = jax.nn.relu(x @ params["w1"])
    out = h @ params["w2"]
    dout = (out - y) / T                      # d(loss)/d(out)
    g2_manual = dout.T @ dout / T * T         # blocked_gram * T tokens
    np.testing.assert_allclose(
        np.asarray(g_grams["w2"][0])[:4, :4], np.asarray(g2_manual),
        rtol=1e-4, atol=1e-7)

    dh = (dout @ params["w2"].T) * (h > 0)
    g1_manual = dh.T @ dh
    np.testing.assert_allclose(
        np.asarray(g_grams["w1"][0]), np.asarray(g1_manual),
        rtol=1e-4, atol=1e-7)


def test_weight_grad_equals_kron_identity():
    """Sanity of the factored view: dL/dW = a^T g for a linear layer."""
    specs, loss_with_taps = _tiny_model()
    r = np.random.default_rng(1)
    T = 12
    params = {"w1": jnp.asarray(r.standard_normal((6, 8)), jnp.float32),
              "w2": jnp.asarray(r.standard_normal((8, 4)), jnp.float32)}
    x = jnp.asarray(r.standard_normal((T, 6)), jnp.float32)
    y = jnp.asarray(r.standard_normal((T, 4)), jnp.float32)
    taps = {"w1": jnp.zeros((T, 8)), "w2": jnp.zeros((T, 4))}

    def loss_of_params(p):
        return loss_with_taps(p, taps, (x, y))[0]

    grads = jax.grad(loss_of_params)(params)
    (_, _), tap_grads = jax.value_and_grad(
        lambda p, t: loss_with_taps(p, t, (x, y)), argnums=1,
        has_aux=True)(params, taps)
    h = jax.nn.relu(x @ params["w1"])
    np.testing.assert_allclose(
        np.asarray(grads["w2"]), np.asarray(h.T @ tap_grads["w2"]),
        rtol=1e-4, atol=1e-7)


# ---------------------------------------------------------------------------
# preconditioning math
# ---------------------------------------------------------------------------

def test_block_precondition_solves_block_system():
    r = np.random.default_rng(2)
    bs, nb_i, nb_o = 8, 2, 1
    d_in, d_out = bs * nb_i, bs * nb_o
    g = jnp.asarray(r.standard_normal((d_in, d_out)), jnp.float32)

    def spd(n):
        m = r.standard_normal((n, n))
        return jnp.asarray(m @ m.T / n + np.eye(n), jnp.float32)

    a_blocks = jnp.stack([spd(bs) for _ in range(nb_i)])
    g_blocks = jnp.stack([spd(bs) for _ in range(nb_o)])
    a_inv = jnp.linalg.inv(a_blocks)
    g_inv = jnp.linalg.inv(g_blocks)
    out = soi.block_precondition(g, a_inv, g_inv)
    # block (i, j) must equal A_i^{-1} g_ij G_j^{-1}
    for i in range(nb_i):
        for j in range(nb_o):
            blk = g[i * bs:(i + 1) * bs, j * bs:(j + 1) * bs]
            want = a_inv[i] @ blk @ g_inv[j]
            np.testing.assert_allclose(
                np.asarray(out[i * bs:(i + 1) * bs,
                               j * bs:(j + 1) * bs]),
                np.asarray(want), rtol=1e-4, atol=1e-5)


def test_refresh_inverses_accuracy():
    r = np.random.default_rng(3)
    cfg = KFACConfig(block_size=16, damping=0.05, ns_iters=22,
                     refine_steps=2)
    specs = {"w": LinearSpec(d_in=32, d_out=16)}
    state = kfac.init({"w": jnp.zeros((32, 16))}, specs, cfg)
    m = r.standard_normal((2, 16, 16)).astype(np.float32)
    a = jnp.asarray(np.einsum("bij,bkj->bik", m, m) / 16)
    g = jnp.asarray(np.einsum("bij,bkj->bik", m[:1], m[:1]) / 16)
    state = state._replace(factors={"w": {"A": a, "G": g}})
    state = kfac.refresh_inverses(state, cfg)
    lam = soi.tikhonov_damping(a, cfg.damping)
    ad = np.asarray(a) + np.asarray(lam)[..., None, None] \
        * np.eye(16, dtype=np.float32)
    resid = np.einsum("bij,bjk->bik",
                      np.asarray(state.inverses["w"]["A_inv"]), ad) \
        - np.eye(16)
    assert np.max(np.abs(resid)) < 1e-2


def test_apply_updates_decreases_quadratic():
    """Preconditioned step on an ill-conditioned quadratic makes far more
    progress than the same-lr plain-gradient step."""
    r = np.random.default_rng(4)
    n = 16
    q = np.linalg.qr(r.standard_normal((n, n)))[0]
    h = (q * np.logspace(-2, 1, n)) @ q.T
    h = jnp.asarray((h + h.T) / 2, jnp.float32)

    cfg = KFACConfig(lr=1.0, momentum=0.0, damping=1e-4,
                     block_size=n, kl_clip=1e9)
    specs = {"w": LinearSpec(d_in=n, d_out=n)}
    w0 = jnp.asarray(r.standard_normal((n, n)), jnp.float32)
    params = {"w": w0}

    def loss(p):
        return 0.5 * jnp.trace(p["w"].T @ h @ p["w"])

    state = kfac.init(params, specs, cfg)
    # feed exact curvature: A = H (input side), G = I
    state = state._replace(factors={"w": {
        "A": h[None], "G": jnp.eye(n)[None]}})
    state = kfac.refresh_inverses(state, cfg)
    grads = jax.grad(loss)(params)
    p2, _ = kfac.apply_updates(params, grads, state, specs, cfg)
    p2_sgd = {"w": params["w"] - cfg.lr / float(
        np.abs(np.linalg.eigvalsh(np.asarray(h))).max())
        * grads["w"]}
    assert float(loss(p2)) < 0.05 * float(loss(params))
    assert float(loss(p2)) < float(loss(p2_sgd))


def test_trust_region_ignores_adam_path_leaves():
    """The kl clip's ``sum(pre * grads)`` must run over factored leaves
    only: on the Adam path ``pre is g``, so a large non-factored
    gradient used to inflate the dot and spuriously shrink ``nu`` for
    the preconditioned step (regression)."""
    r = np.random.default_rng(7)
    n = 8
    cfg = KFACConfig(lr=1.0, momentum=0.0, damping=1e-4, block_size=n,
                     kl_clip=1e-3, weight_decay=0.0)
    specs = {"w": LinearSpec(d_in=n, d_out=n)}
    w = jnp.asarray(r.standard_normal((n, n)), jnp.float32)
    gw = jnp.asarray(r.standard_normal((n, n)), jnp.float32)

    def factored_update(bias_grad_scale):
        params = {"w": w, "b": jnp.zeros((n,), jnp.float32)}
        grads = {"w": gw,
                 "b": jnp.full((n,), bias_grad_scale, jnp.float32)}
        # init's inverses are identity blocks => pre["w"] == gw exactly
        state = kfac.init(params, specs, cfg)
        p2, _ = kfac.apply_updates(params, grads, state, specs, cfg)
        return np.asarray(p2["w"])

    # the factored step must not depend on the Adam-path gradient scale
    # (pre-fix, the 1e4 bias gradient shrank nu by ~7 orders)
    np.testing.assert_allclose(factored_update(0.0),
                               factored_update(1e4), rtol=1e-6)

    # and the clip itself still engages on the factored dot:
    # nu = kl_clip / (lr * |gw|^2) < 1 here, update = -lr * nu * gw
    dot = float(jnp.sum(gw * gw))
    nu = min(1.0, cfg.kl_clip / (cfg.lr * abs(dot) + 1e-12))
    assert nu < 1.0
    np.testing.assert_allclose(
        factored_update(0.0), np.asarray(w - cfg.lr * nu * gw),
        rtol=1e-5)


# ---------------------------------------------------------------------------
# pimsim vs the paper's closed forms
# ---------------------------------------------------------------------------

def test_eqn10_eqn14_cycles():
    from repro.pimsim import crossbar as xb
    from repro.pimsim.arch import RePASTConfig

    c = RePASTConfig()
    # Eqn. 10 with Q=16, Rdac=4, Radc=8, N=18: 18*(2*4*2 + 4) = 360
    assert xb.inv_cycles(c) == 360
    # Eqn. 14: 18*(2*4*2 + 2*4) = 432
    assert xb.inv_fused_cycles(c) == 432


def test_mapping_matches_paper_cases():
    """Fig. 9: a (1024, 256) -> fuse (8 xbars vs 16); a (256, 1024) ->
    materialize (1 xbar vs 8)."""
    from repro.pimsim import mapping
    from repro.pimsim.arch import RePASTConfig

    c = RePASTConfig()
    tall = mapping.mm_inv_choice(c, 1024, 256, block=1024)
    assert tall.fuse and tall.xbars == 8
    wide = mapping.mm_inv_choice(c, 256, 1024, block=1024)
    assert not wide.fuse and wide.xbars == 1


def test_occupation_block_invariance():
    """Sec. VI-E: with the mapping scheme, SOI crossbar occupation is
    asymptotically independent of block size."""
    from repro.pimsim import mapping
    from repro.pimsim.arch import RePASTConfig

    c = RePASTConfig()
    layer = ("conv", (512, 512, 3, 14, 14))    # cin k^2 = 4608, hw=196
    occ = [mapping.soi_xbar_occupation(c, layer, b) for b in
           (512, 1024, 2048, 4608)]
    assert max(occ) <= 2 * min(occ) + 1
    occ_nomap = [mapping.soi_xbar_occupation(c, layer, b, False)
                 for b in (512, 1024, 2048, 4608)]
    assert occ_nomap[-1] > 4 * occ_nomap[0]    # quadratic blowup
