"""Multi-device pipeline execution parity (forced 4-device mesh).

The acceptance contract, operationalized:

* **per-step equivalence at the fp32 floor** — on identical state the
  pipelined (pp=2) gradients match the monolithic accumulation path
  leaf-by-leaf to ~1e-5 relative (measured ~3e-7, pure f32 rounding of
  two different XLA programs computing the same math);
* **schedule independence** — gpipe and 1f1b drive the *same* program
  pieces through different tick orders and must produce identical
  losses (they agree bitwise in practice: grads are summed in
  microbatch order under both);
* **20-step loss-trajectory tracking** — tight (2e-4) over the first 8
  steps; 2e-2 over all 20. The widening is measured chaos: training
  dynamics amplify the per-step 3e-7 rounding floor by ~3-4x/step, so
  *any* two distinct-but-equivalent programs decorrelate to the loss-
  fluctuation scale by ~step 15 (EXPERIMENTS.md §Perf 5.3 records the
  sweep; the per-step grad bound above is the sharp statement of
  correctness).

The unmarked subprocess smoke keeps this coverage inside tier-1; the
multidevice CI job runs the marked tests directly.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.core import kfac as kfac_mod
from repro.core.kfac import KFACConfig
from repro.dist.api import path_key
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_pipeline_mesh
from repro.launch.steps import TrainState
from repro.pipeline import (
    make_pipeline_grads_fn,
    make_schedule,
    partition_stages,
    split_microbatches,
)

M, B, T, STEPS = 4, 8, 16, 20
KCFG = KFACConfig(block_size=32, stats_batch=4, stats_seq=16)


def _setup(arch="qwen1.5-0.5b", dtype="float32"):
    cfg = dataclasses.replace(get_smoke_config(arch), dtype=dtype,
                              train_accum=M)
    mod = steps_mod.model_module(cfg)
    params = mod.init(cfg, jax.random.PRNGKey(0))
    specs = steps_mod.kfac_specs(cfg)
    r = np.random.default_rng(0)
    batches = [{"tokens": jnp.asarray(
        r.integers(0, cfg.vocab, (B, T)), jnp.int32)}
        for _ in range(STEPS)]
    return cfg, params, specs, batches


def _run_traj(cfg, params, specs, batches, step_fn):
    """20 steps with the full K-FAC cadence (stats+inv every 5)."""
    stats = jax.jit(steps_mod.make_stats_step(cfg, KCFG))
    inv = jax.jit(steps_mod.make_inv_step(cfg, KCFG))
    st = TrainState(params, kfac_mod.init(params, specs, KCFG))
    losses = []
    for i, b in enumerate(batches):
        if i % 5 == 0:
            st, _ = stats(st, b)
            st = inv(st)
        st, m = step_fn(st, b)
        losses.append(float(m["loss"]))
    return np.array(losses)


def _accum_ref(cfg, params, micro, n_micro):
    """Meshless gradient-accumulation baseline (the monolithic path)."""
    mod = steps_mod.model_module(cfg)

    def loss_of(p, b):
        return mod.loss_fn(cfg, p, b)[0]

    def accum_grads(p):
        g = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), p)
        tot = jnp.zeros((), jnp.float32)
        for m in range(n_micro):
            mb = jax.tree.map(lambda v: v[m], micro)
            l, gm = jax.value_and_grad(loss_of)(p, mb)
            g = jax.tree.map(lambda a, x: a + x / n_micro, g, gm)
            tot = tot + l / n_micro
        return tot, g

    return jax.jit(accum_grads)(params)


def _assert_grads_close(g_ref, g_pipe, tol=1e-5):
    fb = {path_key(p): v for p, v in
          jax.tree_util.tree_flatten_with_path(g_pipe)[0]}
    for p, v in jax.tree_util.tree_flatten_with_path(g_ref)[0]:
        k = path_key(p)
        a, b = np.asarray(v), np.asarray(fb[k])
        assert a.shape == b.shape, k
        scale = max(np.abs(a).max(), 1e-12)
        assert np.abs(a - b).max() / scale < tol, k


def _pipeline_grads(cfg, params, micro, mesh, n_micro, **part_kw):
    part = partition_stages(cfg, 2, **part_kw)
    sched = make_schedule("1f1b", 2, n_micro)
    fn = make_pipeline_grads_fn(cfg, part, sched, mesh)
    with jax.set_mesh(mesh):
        return jax.jit(fn)(params, micro)


@pytest.mark.multidevice
def test_pp2_grads_match_accum_at_fp32_floor():
    """Pipelined gradients == accumulation gradients, leaf by leaf, at
    the f32 rounding floor — the sharp per-step equivalence."""
    cfg, params, specs, batches = _setup()
    micro = split_microbatches(batches[0], M)
    l1, g1 = _accum_ref(cfg, params, micro, M)
    l2, g2 = _pipeline_grads(cfg, params, micro, make_pipeline_mesh(2),
                             M, require_uniform=True)
    assert abs(float(l1) - float(l2)) < 1e-5
    _assert_grads_close(g1, g2)


@pytest.mark.multidevice
def test_pp2_mp2_grads_match_model_only_baseline():
    """The tentpole parity gate: a forced (stage=2, data=1, model=2)
    mesh — megatron TP inside the stage program (sharded qkv/o and
    mlp, manual psums over the bound ``model`` axis) — reproduces the
    meshless accumulation gradients leaf-by-leaf at the f32 floor."""
    cfg, params, specs, batches = _setup()       # qwen: h=kv=4, ff=128
    micro = split_microbatches(batches[0], M)
    l1, g1 = _accum_ref(cfg, params, micro, M)
    mesh = make_pipeline_mesh(2, model=2)
    assert dict(mesh.shape) == {"stage": 2, "data": 1, "model": 2}
    l2, g2 = _pipeline_grads(cfg, params, micro, mesh, M,
                             require_uniform=True)
    assert abs(float(l1) - float(l2)) < 1e-5
    _assert_grads_close(g1, g2)


@pytest.mark.multidevice
def test_pp2_mp2_moe_ep_in_stage_parity():
    """EP-in-stage == portable dispatch: with data=1 the per-shard
    expert queues see the same tokens in the same order as the global
    scatter reference, so the (stage=2, model=2) program — experts
    sliced over ``model``, dispatch via _local_moe's manual
    collectives — matches the meshless path at the f32 floor."""
    cfg = dataclasses.replace(get_smoke_config("phi3.5-moe-42b-a6.6b"),
                              dtype="float32", train_accum=2)
    mod = steps_mod.model_module(cfg)
    params = mod.init(cfg, jax.random.PRNGKey(0))
    r = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(
        r.integers(0, cfg.vocab, (4, 16)), jnp.int32)}
    micro = split_microbatches(batch, 2)
    l1, g1 = _accum_ref(cfg, params, micro, 2)
    mesh = make_pipeline_mesh(2, model=2)
    l2, g2 = _pipeline_grads(cfg, params, micro, mesh, 2,
                             require_uniform=True)
    assert abs(float(l1) - float(l2)) / abs(float(l1)) < 1e-5
    _assert_grads_close(g1, g2)


@pytest.mark.multidevice
def test_pp2_nonuniform_hybrid_grads():
    """Non-uniform hybrid end-to-end: 3 pattern units + 1 ragged tail
    sublayer on 2 stages — (2, 1) unit split via padding + masks, tail
    + head on the last stage, MLPs TP-sharded (kv=1 keeps attention
    replicated) — matches the monolithic path at the f32 floor."""
    cfg = dataclasses.replace(get_smoke_config("recurrentgemma-9b"),
                              n_layers=10, dtype="float32",
                              train_accum=2)
    mod = steps_mod.model_module(cfg)
    params = mod.init(cfg, jax.random.PRNGKey(0))
    r = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(
        r.integers(0, cfg.vocab, (4, 16)), jnp.int32)}
    micro = split_microbatches(batch, 2)
    l1, g1 = _accum_ref(cfg, params, micro, 2)
    mesh = make_pipeline_mesh(2, model=2)
    part = partition_stages(cfg, 2)
    assert part.atom == "unit" and not part.uniform
    sched = make_schedule("1f1b", 2, 2)
    fn = make_pipeline_grads_fn(cfg, part, sched, mesh)
    with jax.set_mesh(mesh):
        l2, g2 = jax.jit(fn)(params, micro)
    assert abs(float(l1) - float(l2)) / abs(float(l1)) < 1e-5
    _assert_grads_close(g1, g2)


@pytest.mark.multidevice
def test_pp2_nonuniform_whisper_grads():
    """Whisper enc-dec end-to-end: the concatenated [enc|dec] channel
    on a (stage=2, data=2) mesh, encoder atoms on the leading stage,
    decoders trailing, padded+masked stacks — matches the monolithic
    encode+decode path at the f32 floor."""
    cfg = dataclasses.replace(get_smoke_config("whisper-tiny"),
                              dtype="float32", train_accum=2)
    mod = steps_mod.model_module(cfg)
    params = mod.init(cfg, jax.random.PRNGKey(0))
    r = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(
            r.integers(0, cfg.vocab, (4, 16)), jnp.int32),
        "enc_embeds": jnp.asarray(
            r.normal(size=(4, 12, cfg.d_model)), jnp.float32),
    }
    micro = split_microbatches(batch, 2)
    l1, g1 = _accum_ref(cfg, params, micro, 2)
    mesh = make_pipeline_mesh(2)
    part = partition_stages(cfg, 2)
    assert part.atom == "encdec" and not part.uniform
    sched = make_schedule("1f1b", 2, 2)
    fn = make_pipeline_grads_fn(cfg, part, sched, mesh)
    with jax.set_mesh(mesh):
        l2, g2 = jax.jit(fn)(params, micro)
    assert abs(float(l1) - float(l2)) / abs(float(l1)) < 1e-5
    _assert_grads_close(g1, g2)


@pytest.mark.skipif("jax.device_count() < 8")
def test_4d_pp2_dp2_mp2_grads():
    """The full 4D composition on 8 devices: (stage=2, data=2,
    model=2) — pipeline x data x tensor parallelism in one program —
    matches the meshless baseline at the f32 floor. Runs only in the
    8-device subprocess (see the smoke below)."""
    cfg, params, specs, batches = _setup()
    micro = split_microbatches(batches[0], M)
    l1, g1 = _accum_ref(cfg, params, micro, M)
    mesh = make_pipeline_mesh(2, model=2)
    assert dict(mesh.shape) == {"stage": 2, "data": 2, "model": 2}
    l2, g2 = _pipeline_grads(cfg, params, micro, mesh, M,
                             require_uniform=True)
    assert abs(float(l1) - float(l2)) < 1e-5
    _assert_grads_close(g1, g2)


@pytest.mark.multidevice
def test_pp2_trajectory_matches_pp1_both_schedules():
    """pp=2 gpipe/1f1b vs pp=1 over 20 steps: tight while rounding
    noise hasn't amplified, bounded after; gpipe == 1f1b throughout."""
    cfg, params, specs, batches = _setup()
    l1 = _run_traj(cfg, params, specs, batches,
                   jax.jit(steps_mod.make_train_step(cfg, KCFG)))
    assert np.isfinite(l1).all()

    mesh = make_pipeline_mesh(2)
    got = {}
    for kind in ("gpipe", "1f1b"):
        with jax.set_mesh(mesh):
            step = jax.jit(steps_mod.make_pipeline_step(
                cfg, KCFG, mesh=mesh, pp=2, schedule=kind, n_micro=M))
            got[kind] = _run_traj(cfg, params, specs, batches, step)
        np.testing.assert_allclose(l1[:8], got[kind][:8], rtol=2e-4)
        np.testing.assert_allclose(l1, got[kind], rtol=2e-2)
    # schedule independence: the two pipelines agree with each other
    np.testing.assert_allclose(got["gpipe"], got["1f1b"], rtol=1e-6)


@pytest.mark.multidevice
def test_pp2_moe_and_ssm_one_step():
    """Families beyond dense run through the pipeline: ssm matches at
    the fp32 floor; MoE at capacity-rounding (the stage program
    dispatches per data-shard tokens — the EP fast path's per-device
    capacity semantics — vs the meshless reference's global pool)."""
    mesh = make_pipeline_mesh(2)
    for arch, tol in (("falcon-mamba-7b", 1e-5),
                      ("phi3.5-moe-42b-a6.6b", 2e-2)):
        cfg = dataclasses.replace(get_smoke_config(arch),
                                  dtype="float32", train_accum=2)
        mod = steps_mod.model_module(cfg)
        params = mod.init(cfg, jax.random.PRNGKey(0))
        specs = steps_mod.kfac_specs(cfg)
        r = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(
            r.integers(0, cfg.vocab, (4, 16)), jnp.int32)}
        st = TrainState(params, kfac_mod.init(params, specs, KCFG))
        _, m1 = jax.jit(steps_mod.make_train_step(cfg, KCFG))(st, batch)
        st = TrainState(params, kfac_mod.init(params, specs, KCFG))
        with jax.set_mesh(mesh):
            step = jax.jit(steps_mod.make_pipeline_step(
                cfg, KCFG, mesh=mesh, pp=2, schedule="1f1b",
                n_micro=2))
            _, m2 = step(st, batch)
        rel = abs(float(m1["loss"]) - float(m2["loss"])) \
            / abs(float(m1["loss"]))
        assert rel < tol, (arch, rel)


@pytest.mark.multidevice
def test_pp2_train_cli_smoke(tmp_path):
    """End-to-end KFACProgram wiring: --pp 2 + async-inv (bubble
    refresh) through the fault-tolerant loop."""
    from repro.launch.train import main

    summary = main(["--arch", "qwen1.5-0.5b", "--smoke", "--steps",
                    "6", "--batch", "8", "--seq", "32", "--pp", "2",
                    "--pp-schedule", "gpipe", "--async-inv",
                    "--ckpt-dir", str(tmp_path / "ck")])
    assert summary["steps"] == 6
    hist = summary["history"]
    assert any("pp_bubble_fraction" in h for h in hist)
    assert all(np.isfinite(h["loss"]) for h in hist if "loss" in h)


@pytest.mark.skipif(jax.device_count() >= 4,
                    reason="marked tests already run in this session")
def test_multidevice_subprocess_smoke(multidev_runner):
    """Tier-1 coverage of the marked tests: re-run them in a child
    process with a forced 4-device host platform."""
    proc = multidev_runner(
        ["-m", "multidevice", "tests/test_pipeline_multidev.py"])
    tail = (proc.stdout + proc.stderr)[-3000:]
    assert proc.returncode == 0, tail
    assert "passed" in proc.stdout, tail
    assert "skipped" not in proc.stdout, tail


@pytest.mark.skipif(jax.device_count() >= 8,
                    reason="8-device session runs the 4D test directly")
def test_8dev_4d_subprocess_smoke(multidev_runner):
    """Tier-1 coverage of the full (stage=2, data=2, model=2) program:
    run the 4D parity test in a child with 8 forced devices."""
    proc = multidev_runner(
        ["tests/test_pipeline_multidev.py::test_4d_pp2_dp2_mp2_grads"],
        ndev=8)
    tail = (proc.stdout + proc.stderr)[-3000:]
    assert proc.returncode == 0, tail
    assert "1 passed" in proc.stdout, tail
