"""MoE datapath equivalence: the shard_map fast path (EP dispatch, one
psum) must match the reference global-scatter path — same top-k, same
capacity-union semantics — and both must drop overflow tokens
identically when capacity binds."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.models import lm, moe


def _mesh(shape=(2, 2)):
    if jax.device_count() < shape[0] * shape[1]:
        pytest.skip(f"needs {shape[0] * shape[1]} devices "
                    f"(run under --xla_force_host_platform_device_count)")
    return jax.make_mesh(
        shape, ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)


def test_fast_path_selection():
    cfg = get_smoke_config("phi3.5-moe-42b-a6.6b")
    from repro.models.layers import Ctx

    # no mesh -> reference
    assert not moe._use_fast_path(cfg, None, "layers/moe")
    # collect/taps -> reference even under a mesh (SU graph)
    ctx = Ctx(collect=True)
    assert not moe._use_fast_path(cfg, ctx, "layers/moe")


def test_reference_path_capacity_and_drop():
    cfg = get_smoke_config("phi3.5-moe-42b-a6.6b")
    params = lm.init(cfg, jax.random.PRNGKey(0))
    r = np.random.default_rng(1)
    batch = {"tokens": jnp.asarray(
        r.integers(0, cfg.vocab, (2, 16)), jnp.int32)}
    loss, _ = jax.jit(lambda p, b: lm.loss_fn(cfg, p, b))(params, batch)
    assert np.isfinite(float(loss))


def test_moe_capacity_math():
    cfg = get_smoke_config("phi3.5-moe-42b-a6.6b")
    c = moe.capacity(cfg, 1024)
    assert c >= 8 and c % 8 == 0
    expect = cfg.capacity_factor * 1024 * cfg.top_k / cfg.n_experts
    assert c >= int(expect) - 8
