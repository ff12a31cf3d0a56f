"""Moonlight-16B-A3B (MLA, dropless sigmoid-routed experts with shared
experts, a leading dense layer) against its plain reference
(``bench/reference/moonlight.py``, loaded by path), on the CPU at a small
size with seeded random weights.

The program runs in float32 here, so the comparison is of formulations,
not of precisions: the reference applies every held expert to every
token and masks by the routing weight, the program sorts the (token,
choice) pairs and runs grouped matmuls over the held experts' rows.
Tolerances: rtol 1e-4 on the loss and 2e-4 on gradients, factors and
the stepped weights: float32 summation order differs between the two
formulations (per-expert sums over sorted rows against masked sums over
all tokens), which moves the last few bits of the 64-wide sums, and the
K-FAC step amplifies those through the block inverses.
"""

import dataclasses
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.core import kfac
from repro.core.kfac import KFACConfig
from repro.launch import steps as steps_mod
from repro.models import lm, moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    import importlib.util

    path = os.path.join(ROOT, "bench", "reference", "moonlight.py")
    spec = importlib.util.spec_from_file_location("ref_moonlight", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()

#: three MoE layers after one dense layer; 4 of 8 experts held from the
#: second, top-2; every width small
CFG = dataclasses.replace(
    get_smoke_config("moonlight-16b-a3b"), n_layers=4, experts_held=4,
    expert_first=2, dtype="float32")
HP = {"lr": 3e-3, "damping": 0.03, "momentum": 0.9, "ema_decay": 0.95,
      "kl_clip": 1.0, "weight_decay": 0.0, "adam_b1": 0.9,
      "adam_b2": 0.999, "adam_eps": 1e-8}
B, T = 2, 32


def _arch(cfg):
    return {
        "n_layers": cfg.n_layers, "d_model": cfg.d_model,
        "n_heads": cfg.n_heads, "d_ff": cfg.d_ff, "vocab": cfg.vocab,
        "rope_theta": cfg.rope_theta, "norm_eps": cfg.norm_eps,
        "kv_lora_rank": cfg.kv_lora_rank, "qk_nope_dim": cfg.qk_nope_dim,
        "qk_rope_dim": cfg.qk_rope_dim, "v_head_dim": cfg.v_head_dim,
        "n_experts": cfg.n_experts, "experts_held": cfg.held,
        "expert_first": cfg.expert_first, "top_k": cfg.top_k,
        "n_shared_experts": cfg.n_shared_experts,
        "d_expert": cfg.d_expert_, "n_dense_layers": cfg.n_dense_layers,
        "routed_scale": cfg.routed_scale,
    }


ARCH = _arch(CFG)


def _tokens(seed=1):
    r = np.random.default_rng(seed)
    return jnp.asarray(r.integers(0, CFG.vocab, (B, T)), jnp.int32)


def _by_path(tree):
    return REF.leaves_by_path(tree)


def _close(got, want, rtol, what):
    got, want = _by_path(got), _by_path(want)
    assert set(got) == set(want), (what, set(got) ^ set(want))
    for k in want:
        scale = float(jnp.max(jnp.abs(want[k]))) or 1.0
        err = float(jnp.max(jnp.abs(got[k] - want[k])))
        assert err <= rtol * scale, (what, k, err, scale)


def _kcfg():
    return KFACConfig(block_size=CFG.soi_block, stats_batch=B,
                      stats_seq=T, inv_method="exact", **HP)


def test_init_matches_reference_leaf_by_leaf():
    key = REF.key_for(7)
    _close(lm.init(CFG, key), REF.init(ARCH, key), 0.0, "init")


def test_loss_and_gradients_match_reference():
    key, tokens = REF.key_for(3), _tokens()
    params = lm.init(CFG, key)
    model = REF.Model(ARCH, CFG.soi_block, REF.Prec("reference"))
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: lm.loss_fn(CFG, p, {"tokens": tokens})[0]))(params)
        ref_loss, ref_grads = jax.jit(model.grads)(params, tokens)
    assert abs(float(loss) - float(ref_loss)) <= 1e-4 * abs(
        float(ref_loss))
    _close(grads, ref_grads, 2e-4, "grad")


def _program_steps(params, tokens, steps=("stats", "inv", "train")):
    kc = _kcfg()
    specs = steps_mod.kfac_specs(CFG)
    state = steps_mod.TrainState(params, kfac.init(params, specs, kc))
    batch = {"tokens": tokens}
    with jax.default_matmul_precision("highest"):
        if "stats" in steps:
            state, _ = jax.jit(steps_mod.make_stats_step(CFG, kc))(
                state, batch)
        if "inv" in steps:
            state = jax.jit(steps_mod.make_inv_step(CFG, kc))(state)
        if "train" in steps:
            wu = steps_mod.make_wu_plan_for(CFG, kc)
            state, _ = jax.jit(steps_mod.make_train_step(
                CFG, kc, wu_plan=wu))(state, batch)
    return state


def _reference_steps(params, tokens, steps=("stats", "inv", "train")):
    model = REF.Model(ARCH, CFG.soi_block, REF.Prec("reference"))
    opt = REF.KFAC(model, dict(HP))
    st = opt.init_state(params)
    st["step"] = jnp.zeros((), jnp.int32)
    with jax.default_matmul_precision("highest"):
        if "stats" in steps:
            a, g, _ = jax.jit(model.stats)(params, tokens)
            st["factors"] = opt.update_factors(st["factors"], a, g)
        if "inv" in steps:
            st["inverses"] = opt.invert(st["factors"])
        if "train" in steps:
            _, grads = jax.jit(model.grads)(params, tokens)
            params, st = jax.jit(opt.apply)(params, grads, st)
    return params, st


def test_statistics_factors_match_reference():
    key, tokens = REF.key_for(5), _tokens(2)
    params = lm.init(CFG, key)
    got = _program_steps(params, tokens, ("stats",))
    _, st = _reference_steps(params, tokens, ("stats",))
    _close(got.kfac.factors, st["factors"], 2e-4, "factor")
    # the held experts' factors are per expert, over their own tokens
    assert got.kfac.factors["layers/moe/wg"]["A"].shape[:2] == (
        CFG.n_moe_layers, CFG.held)


def test_one_kfac_step_matches_reference():
    key, tokens = REF.key_for(11), _tokens(4)
    params = lm.init(CFG, key)
    got = _program_steps(params, tokens)
    ref_params, st = _reference_steps(params, tokens)
    _close(got.kfac.inverses, st["inverses"], 2e-4, "inverse")
    _close(got.params, ref_params, 2e-4, "params")


def _layer_params(cfg, key):
    return {"moe": moe.init_share(cfg, key),
            "shared": lm._init_mlp(cfg, jax.random.fold_in(key, 1),
                                   cfg.n_shared_experts * cfg.d_expert_)}


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four shares of 2 of 8 experts: their routed parts, with the
    shared experts counted once, add up to the uncut reference layer."""
    full = dataclasses.replace(CFG, experts_held=8, expert_first=0)
    key = jax.random.PRNGKey(0)
    p = _layer_params(full, key)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, full.d_model))
    with jax.default_matmul_precision("highest"):
        total = 0.0
        for first in range(0, 8, 2):
            cfg = dataclasses.replace(full, experts_held=2,
                                      expert_first=first)
            share = jax.tree.map(lambda w: w[first:first + 2]
                                 if w.ndim == 3 else w, p["moe"])
            # a share's own init holds the same experts' weights
            own = moe.init_share(cfg, key)
            _close(own, share, 0.0, "share init")
            total = total + moe.share_ffn(cfg, share, x, None, "layers/moe")
        total = total + lm._ffn(full, p["shared"], x, None, "layers/shared",
                                full.n_shared_experts * full.d_expert_)
        model = REF.Model(_arch(full), full.soi_block, REF.Prec("reference"))
        want = jax.vmap(lambda xr: model._experts(xr, p["moe"], None, None,
                                                  None)
                        + model._mlp(xr, p["shared"], "layers/shared",
                                     None, None))(x)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_routing_selects_by_biased_score_and_weights_by_unbiased():
    cfg = dataclasses.replace(CFG, experts_held=8, expert_first=0)
    p = moe.init_share(cfg, jax.random.PRNGKey(0))
    # a bias that lifts experts 6 and 7 above every unbiased score
    p = dict(p, bias=jnp.zeros((8,)).at[6].set(5.0).at[7].set(4.0))
    x = jax.random.normal(jax.random.PRNGKey(2), (16, cfg.d_model))
    w, eid = moe.route(cfg, p, x)
    scores = jax.nn.sigmoid(jnp.dot(x, p["router"],
                                    precision=jax.lax.Precision.HIGHEST))
    assert (np.sort(np.asarray(eid), -1) == [6, 7]).all()
    want = scores[:, 6:8] / jnp.sum(scores[:, 6:8], -1, keepdims=True) \
        * cfg.routed_scale
    got = jnp.take_along_axis(w, jnp.argsort(eid, -1), -1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(jnp.sum(w, -1)),
                               cfg.routed_scale, rtol=1e-6)


def test_dropless_every_token_to_the_same_experts():
    """Every token routed to the same two held experts: no capacity, so
    every (token, choice) pair is computed, and the layer equals the
    reference's dense formulation."""
    cfg = dataclasses.replace(CFG, experts_held=4, expert_first=0)
    p = moe.init_share(cfg, jax.random.PRNGKey(0))
    p = dict(p, bias=jnp.zeros((cfg.n_experts,)).at[1].set(9.0)
             .at[3].set(9.0))
    x = jax.random.normal(jax.random.PRNGKey(3), (B, T, cfg.d_model))
    from repro.models.layers import Ctx

    ctx = Ctx(collect=True, soi_block=cfg.soi_block)
    with jax.default_matmul_precision("highest"):
        got = moe.share_ffn(cfg, p, x, ctx, "layers/moe")
        model = REF.Model(_arch(cfg), cfg.soi_block, REF.Prec("reference"))
        want = jax.vmap(lambda xr: model._experts(xr, p, None, None,
                                                  None))(x)
    counts = np.asarray(ctx.stats["layers/moe/counts"])
    assert counts.tolist() == [0, B * T, 0, B * T]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_stats_step_exports_the_expert_counters():
    params = lm.init(CFG, REF.key_for(2))
    kc = _kcfg()
    specs = steps_mod.kfac_specs(CFG)
    state = steps_mod.TrainState(params, kfac.init(params, specs, kc))
    _, m = jax.jit(steps_mod.make_stats_step(CFG, kc))(
        state, {"tokens": _tokens()})
    held = float(m["moe_held_tokens"])
    assert 0 < held <= B * T * CFG.top_k
    assert float(m["moe_busiest_over_mean"]) >= 1.0


def test_serving_refuses_latent_attention():
    with pytest.raises(NotImplementedError, match="latent cache"):
        lm.init_cache(CFG, 2, 16)


def test_pipeline_refuses_the_dropless_layer():
    with pytest.raises(NotImplementedError, match="dropless expert exchange"):
        steps_mod.make_pipeline_step(CFG, _kcfg(), mesh=object(), pp=2)


def test_model_axis_refuses_the_dropless_layer():
    mesh = jax.sharding.AbstractMesh(
        (1, 2), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)
    p = moe.init_share(CFG, jax.random.PRNGKey(0))
    x = jnp.zeros((B, T, CFG.d_model))
    with jax.sharding.use_abstract_mesh(mesh), pytest.raises(
            NotImplementedError, match="exchange"):
        moe.share_ffn(CFG, p, x, None, "layers/moe")
