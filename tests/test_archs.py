"""Per-architecture smoke tests: reduced configs, one forward/train step
on CPU, asserting output shapes and finiteness (assignment deliverable f).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, get_smoke_config
from repro.models import lm, whisper

B, T = 2, 32


def _batch(cfg, key):
    ks = jax.random.split(key, 3)
    batch = {"tokens": jax.random.randint(ks[0], (B, T), 0, cfg.vocab)}
    if cfg.family == "vlm":
        n_img = cfg.n_img_tokens
        batch["img_embeds"] = jax.random.normal(
            ks[1], (B, n_img, cfg.vision_dim), jnp.float32)
        pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        batch["positions"] = jnp.stack([pos, pos, pos])
    if cfg.family == "audio":
        batch["enc_embeds"] = jax.random.normal(
            ks[2], (B, T, cfg.d_model), jnp.float32)
    return batch


def _mod(cfg):
    return whisper if cfg.family == "audio" else lm


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_and_grad(arch):
    cfg = get_smoke_config(arch)
    mod = _mod(cfg)
    key = jax.random.PRNGKey(0)
    params = mod.init(cfg, key)
    batch = _batch(cfg, jax.random.PRNGKey(1))

    loss, _ = jax.jit(
        lambda p, b: mod.loss_fn(cfg, p, b))(params, batch)
    assert np.isfinite(float(loss))

    grads = jax.jit(jax.grad(
        lambda p, b: mod.loss_fn(cfg, p, b)[0]))(params, batch)
    gnorm = sum(float(jnp.sum(g * g)) for g in jax.tree.leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_one_sgd_step_reduces_structure(arch):
    """One SGD step runs and changes params finitely."""
    from repro.optim import SGD
    cfg = get_smoke_config(arch)
    mod = _mod(cfg)
    params = mod.init(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    opt = SGD(lr=1e-2)
    state = opt.init(params)

    @jax.jit
    def step(p, s, b):
        loss, grads = jax.value_and_grad(
            lambda q: mod.loss_fn(cfg, q, b)[0])(p)
        p2, s2 = opt.update(grads, s, p)
        return loss, p2, s2

    loss, p2, _ = step(params, state, batch)
    assert np.isfinite(float(loss))
    delta = sum(float(jnp.max(jnp.abs(a - b)))
                for a, b in zip(jax.tree.leaves(params),
                                jax.tree.leaves(p2)))
    assert np.isfinite(delta) and delta > 0


@pytest.mark.parametrize("arch", [a for a in ARCHS
                                  if a != "whisper-tiny"])
def test_prefill_decode_consistency(arch):
    """Prefill+decode must reproduce the teacher-forced logits."""
    import dataclasses
    cfg = get_smoke_config(arch)
    if cfg.mla:
        # latent attention has no serving cache: refused, by name
        with pytest.raises(NotImplementedError, match="latent cache"):
            lm.init_cache(cfg, B, T)
        return
    if cfg.family == "moe":
        # capacity dropping is sequence-length dependent; make dispatch
        # lossless so teacher-forced and incremental paths agree exactly
        cfg = dataclasses.replace(cfg,
                                  capacity_factor=float(cfg.n_experts))
    params = lm.init(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    if cfg.family == "vlm":
        batch.pop("positions")      # serve path uses 1-D positions
        batch.pop("img_embeds")

    full_logits, _, _ = jax.jit(
        lambda p, b: lm.forward(cfg, p, b))(params, batch)

    S = T + 4
    cache = lm.init_cache(cfg, B, S, dtype=jnp.float32)
    t0 = T // 2
    logits_p, cache = jax.jit(lambda p, b, c: lm.prefill(cfg, p, b, c))(
        params, {"tokens": batch["tokens"][:, :t0]}, cache)
    np.testing.assert_allclose(
        np.asarray(logits_p), np.asarray(full_logits[:, t0 - 1]),
        rtol=2e-2, atol=2e-2)

    step = jax.jit(lambda p, t, c: lm.decode_step(cfg, p, t, c))
    for i in range(t0, min(t0 + 3, T)):
        logits_d, cache = step(params, batch["tokens"][:, i:i + 1], cache)
        np.testing.assert_allclose(
            np.asarray(logits_d), np.asarray(full_logits[:, i]),
            rtol=2e-2, atol=2e-2)


def test_whisper_prefill_decode():
    cfg = get_smoke_config("whisper-tiny")
    params = whisper.init(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))

    enc_out, _ = whisper.encode(cfg, params, batch["enc_embeds"])
    full_logits, _, _ = whisper.decode(cfg, params, batch["tokens"],
                                       enc_out)

    cache = whisper.init_cache(cfg, B, T + 4, T, dtype=jnp.float32)
    t0 = T // 2
    logits_p, cache = whisper.prefill(
        cfg, params, {"enc_embeds": batch["enc_embeds"],
                      "tokens": batch["tokens"][:, :t0]}, cache)
    np.testing.assert_allclose(
        np.asarray(logits_p), np.asarray(full_logits[:, t0 - 1]),
        rtol=2e-2, atol=2e-2)
    for i in range(t0, t0 + 2):
        logits_d, cache = whisper.decode_step(
            cfg, params, batch["tokens"][:, i:i + 1], cache)
        np.testing.assert_allclose(
            np.asarray(logits_d), np.asarray(full_logits[:, i]),
            rtol=2e-2, atol=2e-2)


def test_param_counts_sane():
    from repro.configs import get_config
    # spot-check the analytic parameter counts against public numbers
    approx = {
        "qwen2.5-32b": 32e9,
        "llama3.2-1b": 1.2e9,
        "qwen2-0.5b": 0.5e9,
        "falcon-mamba-7b": 7.3e9,
        "qwen2-vl-7b": 7.6e9,
    }
    for name, expect in approx.items():
        n = get_config(name).param_count()
        assert 0.5 * expect < n < 1.7 * expect, (name, n, expect)
