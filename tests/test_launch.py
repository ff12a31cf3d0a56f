"""Launch layer: step builders run concretely on CPU (reduced configs);
HLO analyzer unit behavior; dry-run machinery on a tiny in-process mesh;
roofline math."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.configs.base import ShapeCfg
from repro.core import kfac as kfac_mod
from repro.core.kfac import KFACConfig
from repro.launch import hlo_analysis, roofline
from repro.launch import steps as steps_mod
from repro.launch.steps import TrainState


KCFG = KFACConfig(block_size=32, stats_batch=2, stats_seq=16,
                  stats_every=2, inv_every=2)


def _state(cfg):
    mod = steps_mod.model_module(cfg)
    params = mod.init(cfg, jax.random.PRNGKey(0))
    specs = steps_mod.kfac_specs(cfg)
    return TrainState(params, kfac_mod.init(params, specs, KCFG))


def _batch(cfg, b=2, t=16):
    batch = {"tokens": jnp.zeros((b, t), jnp.int32)}
    if cfg.family == "vlm":
        batch["img_embeds"] = jnp.zeros(
            (b, cfg.n_img_tokens, cfg.vision_dim), jnp.float32)
        pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
        batch["positions"] = jnp.stack([pos, pos, pos])
    if cfg.family == "audio":
        batch["enc_embeds"] = jnp.zeros((b, t, cfg.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "falcon-mamba-7b",
                                  "moonlight-16b-a3b",
                                  "whisper-tiny"])
def test_train_stats_inv_steps_run(arch):
    cfg = get_smoke_config(arch)
    state = _state(cfg)
    batch = _batch(cfg)
    state, m = jax.jit(steps_mod.make_train_step(cfg, KCFG))(state, batch)
    assert np.isfinite(float(m["loss"]))
    state, m2 = jax.jit(steps_mod.make_stats_step(cfg, KCFG))(state, batch)
    assert np.isfinite(float(m2["stats_loss"]))
    state = jax.jit(steps_mod.make_inv_step(cfg, KCFG))(state)
    # factors became non-zero, inverses non-identity for touched blocks
    some_factor = next(iter(jax.tree.leaves(state.kfac.factors)))
    assert float(jnp.max(jnp.abs(some_factor))) > 0
    assert int(state.kfac.step) == 1


def test_train_step_reduces_loss_same_batch():
    cfg = get_smoke_config("qwen1.5-0.5b")
    state = _state(cfg)
    r = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(
        r.integers(0, cfg.vocab, (2, 16)), jnp.int32)}
    train = jax.jit(steps_mod.make_train_step(cfg, KCFG))
    losses = []
    for _ in range(8):
        state, m = train(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


# ---------------------------------------------------------------------------
# HLO analyzer
# ---------------------------------------------------------------------------

def test_hlo_analysis_counts_scan_trips():
    """A scanned matmul must be counted length x, not once."""
    L, n = 7, 32

    def f(x, ws):
        def body(c, w):
            return jnp.dot(c, w, preferred_element_type=jnp.float32), None
        out, _ = jax.lax.scan(body, x, ws)
        return out

    x = jnp.zeros((n, n), jnp.float32)
    ws = jnp.zeros((L, n, n), jnp.float32)
    compiled = jax.jit(f).lower(x, ws).compile()
    mc = hlo_analysis.analyze_text(compiled.as_text())
    want = 2.0 * n * n * n * L
    assert want * 0.99 <= mc.flops <= want * 1.5, mc.flops


def test_hlo_analysis_nested_scan_trips():
    """Nested scans multiply: inner (K) x outer (L) trip counts."""
    L, K, n = 5, 3, 16

    def f(x, ws):
        def outer(c, w):
            def inner(ci, _):
                return jnp.dot(ci, w,
                               preferred_element_type=jnp.float32), None
            c2, _ = jax.lax.scan(inner, c, None, length=K)
            return c2, None
        out, _ = jax.lax.scan(outer, x, ws)
        return out

    x = jnp.zeros((n, n), jnp.float32)
    ws = jnp.zeros((L, n, n), jnp.float32)
    compiled = jax.jit(f).lower(x, ws).compile()
    mc = hlo_analysis.analyze_text(compiled.as_text())
    want = 2.0 * n ** 3 * L * K
    assert want * 0.99 <= mc.flops <= want * 1.6, mc.flops


def test_hlo_analysis_single_dot():
    a = jnp.zeros((64, 128), jnp.float32)
    b = jnp.zeros((128, 32), jnp.float32)
    compiled = jax.jit(
        lambda a, b: jnp.dot(a, b, preferred_element_type=jnp.float32)
    ).lower(a, b).compile()
    mc = hlo_analysis.analyze_text(compiled.as_text())
    assert mc.flops == pytest.approx(2 * 64 * 128 * 32, rel=0.01)
    # traffic at least reads a, b and writes out once
    min_traffic = (64 * 128 + 128 * 32 + 64 * 32) * 4
    assert mc.traffic_bytes >= min_traffic


def test_collective_bytes_parse():
    txt = """
HloModule m

ENTRY %main (p: f32[64,4]) -> f32[64,4] {
  %p = f32[64,4]{1,0} parameter(0)
  %ar = f32[64,4]{1,0} all-reduce(%p), channel_id=1, replica_groups=[4,8]<=[32], to_apply=%add
  %ag = f32[64,32]{1,0} all-gather(%ar), channel_id=2, replica_groups=[4,8]<=[32], dimensions={1}
  ROOT %out = f32[64,4]{1,0} reduce-scatter(%ag), channel_id=3, replica_groups=[4,8]<=[32], dimensions={1}
}
"""
    got = roofline.collective_bytes(txt)
    assert got["all-reduce"] == 64 * 4 * 4
    assert got["all-gather"] == 64 * 32 * 4 // 8
    assert got["reduce-scatter"] == 64 * 4 * 4 * 8


def test_roofline_terms_and_bottleneck():
    r = roofline.Roofline(
        flops_per_dev=197e12, bytes_per_dev=819e9 * 2,
        coll_bytes_per_dev=50e9 * 0.5, coll_breakdown={},
        peak_hbm_per_dev=1e9, chips=256)
    assert r.t_compute == pytest.approx(1.0)
    assert r.t_memory == pytest.approx(2.0)
    assert r.t_collective == pytest.approx(0.5)
    assert r.bottleneck == "memory"


def test_model_flops_conventions():
    from repro.configs import get_config

    cfg = get_config("llama3.2-1b")
    train = ShapeCfg("t", 128, 4, "train")
    dec = ShapeCfg("d", 128, 4, "decode")
    n = cfg.active_param_count()
    assert roofline.model_flops(cfg, train) == pytest.approx(
        6.0 * n * 4 * 128)
    assert roofline.model_flops(cfg, dec) == pytest.approx(2.0 * n * 4)


def test_cell_skip_reasons():
    from repro.configs import get_config
    from repro.configs.base import SHAPES

    full_attn = get_config("qwen2.5-32b")
    ssm = get_config("falcon-mamba-7b")
    assert steps_mod.cell_skip_reason(full_attn, SHAPES["long_500k"])
    assert steps_mod.cell_skip_reason(ssm, SHAPES["long_500k"]) is None
    assert steps_mod.cell_skip_reason(full_attn, SHAPES["train_4k"]) \
        is None


# ---------------------------------------------------------------------------
# dry-run machinery on a tiny mesh (in-process; smoke configs)
# ---------------------------------------------------------------------------

def test_build_cell_lowers_on_dev_mesh():
    cfg = get_smoke_config("qwen2-0.5b")
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    shape = ShapeCfg("tiny_train", 16, 2, "train")
    cells = steps_mod.build_cell(cfg, shape, mesh, KCFG)
    with jax.set_mesh(mesh):
        for cell in cells:
            compiled = cell.lower().compile()
            roof = roofline.analyze(None, compiled, 1)
            assert roof.flops_per_dev > 0
            assert roof.bytes_per_dev > 0


def test_build_cell_decode_lowers():
    cfg = get_smoke_config("qwen2-0.5b")
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    shape = ShapeCfg("tiny_dec", 32, 2, "decode")
    cells = steps_mod.build_cell(cfg, shape, mesh)
    with jax.set_mesh(mesh):
        for cell in cells:
            cell.lower().compile()
