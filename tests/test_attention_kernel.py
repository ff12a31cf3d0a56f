"""The flash attention path (``kernels/flash_attention``), in interpret
mode on the CPU, against the XLA path of ``models/layers.attention``;
and the calls that must keep the XLA path, bit for bit.

Tolerances. Both paths take bf16 operands with f32 accumulation, scale
the f32 scores, keep f32 softmax statistics and cast their probabilities
to bf16 for PV; they differ in where those probabilities are rounded
from (the kernel's come from a running max, rescaled block by block),
in the order the keys are summed, and in the backward, which the kernel
recomputes from the saved logsumexp and whose probabilities and score
gradients it casts to bf16 for its products. Each of these is a bf16
rounding (2^-9 relative) or less, and both round their results to bf16.
So outputs agree to a few bf16 roundings: 2^-7 of their norm, and
gradients, which pass through two such products, to 2^-6 (measured:
2^-8.4 and 2^-8.2 at most).
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_smoke_config
from repro.kernels import flash_attention
from repro.models import lm
from repro.models import layers
from repro.models.layers import attention

B, T, HD = 2, 256, 64
#: the model tests' length: one block of the kernel
T_MODEL = 128
OUT_TOL, GRAD_TOL = 2.0 ** -7, 2.0 ** -6


def _qkv(h, hkv, t=T, seed=0, hd=HD):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, t, h, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, t, hkv, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, t, hkv, hd), jnp.bfloat16)
    ct = jax.random.normal(ks[3], (B, t, h, hd), jnp.float32)
    return q, k, v, ct


def _rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _pos(t=T):
    return jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (B, t))


@pytest.mark.parametrize("h, hkv, block, hd", [
    (4, 4, None, HD), (7, 1, None, HD), (4, 2, None, HD),
    # two blocks a side: the skipped and the clamped blocks, all kernels
    (4, 2, 128, HD),
    # a scale that is no power of two (qwen2.5-32b, the MoE configs)
    (4, 2, 128, 128),
], ids=["mha-4on4", "gqa-7on1", "gqa-4on2", "gqa-4on2-block128",
        "gqa-4on2-block128-hd128"])
def test_kernel_matches_xla_attention(h, hkv, block, hd):
    q, k, v, ct = _qkv(h, hkv, hd=hd)
    pos = _pos()

    def xla(q, k, v):
        return attention(q, k, v, pos, pos)

    def kernel(q, k, v):
        return flash_attention.causal_flash_attention(
            q, k, v, block=block, interpret=True)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32)
                                       * ct)

    want, got = jax.jit(xla)(q, k, v), jax.jit(kernel)(q, k, v)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert _rel(got, want) < OUT_TOL
    g_want = jax.jit(jax.grad(loss(xla), argnums=(0, 1, 2)))(q, k, v)
    g_got = jax.jit(jax.grad(loss(kernel), argnums=(0, 1, 2)))(q, k, v)
    for name, a, b in zip("qkv", g_got, g_want):
        assert a.dtype == b.dtype, name
        assert _rel(a, b) < GRAD_TOL, name


@pytest.mark.parametrize("dqk, dv", [(192, 128), (64, 64)],
                         ids=["mla-192-128", "equal-64"])
def test_kernel_value_width_apart_from_query_key(dqk, dv):
    """Value heads narrower than the query/key heads (latent attention:
    192 and 128, scale 192 ** -0.5, no power of two), two 128 blocks a
    side, against the XLA path; and the equal widths through the same
    entry. Tolerances as above: the narrower value heads change no
    rounding step, only the widths of the PV and dV products."""
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (B, T, 4, dqk), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, T, 4, dqk), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, T, 4, dv), jnp.bfloat16)
    ct = jax.random.normal(ks[3], (B, T, 4, dv), jnp.float32)
    pos = _pos()

    def xla(q, k, v):
        return attention(q, k, v, pos, pos)

    def kernel(q, k, v):
        return flash_attention.causal_flash_attention(
            q, k, v, block=128, interpret=True)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32)
                                       * ct)

    want, got = jax.jit(xla)(q, k, v), jax.jit(kernel)(q, k, v)
    assert got.shape == want.shape == (B, T, 4, dv)
    assert _rel(got, want) < OUT_TOL
    g_want = jax.jit(jax.grad(loss(xla), argnums=(0, 1, 2)))(q, k, v)
    g_got = jax.jit(jax.grad(loss(kernel), argnums=(0, 1, 2)))(q, k, v)
    for name, a, b in zip("qkv", g_got, g_want):
        assert a.shape == b.shape, name
        assert _rel(a, b) < GRAD_TOL, name


def _cfg():
    return dataclasses.replace(get_smoke_config("qwen2-0.5b"), head_dim=HD)


def _has_kernel(fn, *args):
    text = str(jax.make_jaxpr(fn)(*args))
    return "pallas_call" in text


@pytest.mark.parametrize("case", ["window", "not-a-block-multiple",
                                  "not-causal", "longer-keys",
                                  "explicit-positions"])
def test_ineligible_attention_is_the_xla_path(case):
    """Each call the kernel cannot take traces no kernel and returns, to
    the bit, what the XLA path returns."""
    t = 192 if case == "not-a-block-multiple" else T
    q, k, v, _ = _qkv(4, 2, t=t)
    pos = _pos(t)
    kw = {"window": dict(window=64), "not-causal": dict(causal=False)
          }.get(case, {})
    kv_pos = pos
    if case == "longer-keys":
        # a cache: keys past the queries, written at later positions
        k, v = (jnp.concatenate([x, x], axis=1) for x in (k, v))
        kv_pos = jnp.concatenate([pos, pos + t], axis=1)
    implicit = case != "explicit-positions"

    def call(implicit):
        return jax.jit(lambda q, k, v: attention(
            q, k, v, pos, kv_pos, implicit_positions=implicit, **kw))

    assert not _has_kernel(call(implicit), q, k, v)
    assert (call(implicit)(q, k, v) == call(False)(q, k, v)).all()


def test_model_calls_with_a_cache_or_positions_trace_no_kernel():
    cfg = _cfg()
    params = lm.init(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, T_MODEL), 0,
                              cfg.vocab)
    cache = lm.init_cache(cfg, B, 2 * T_MODEL)
    assert not _has_kernel(lambda p, c: lm.prefill(
        cfg, p, {"tokens": toks}, c), params, cache)
    assert not _has_kernel(lambda p: lm.forward(
        cfg, p, {"tokens": toks, "positions": _pos(T_MODEL)}), params)
    # the train forward over the implicit positions does trace it
    assert _has_kernel(lambda p: lm.forward(cfg, p, {"tokens": toks}),
                       params)


def test_eligible_forward_on_the_cpu_is_bitwise_the_xla_path():
    """Lowered for the CPU, the eligible train step takes the XLA branch:
    loss and gradients equal those of the same step given its positions,
    which the kernel never takes."""
    cfg = _cfg()
    params = lm.init(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, T_MODEL), 0,
                              cfg.vocab)
    step = jax.jit(jax.value_and_grad(
        lambda p, b: lm.loss_fn(cfg, p, b)[0]))
    l1, g1 = step(params, {"tokens": toks})
    l2, g2 = step(params, {"tokens": toks, "positions": _pos(T_MODEL)})
    assert l1 == l2
    assert all((a == b).all() for a, b in zip(jax.tree.leaves(g1),
                                              jax.tree.leaves(g2)))


def test_block_size_divides_the_sequence():
    for t in (128, 256, 384, 1024, 1536, 4096):
        b = flash_attention.block_size(t)
        assert b % flash_attention.MIN_BLOCK == 0 and t % b == 0, t
        assert b <= flash_attention.MAX_BLOCK, t
    assert flash_attention.block_size(1536) == 768
    assert flash_attention.supports(256, 256)
    assert not flash_attention.supports(192, 192)
    assert not flash_attention.supports(256, 512)


def test_rotary_half_swap_is_exact():
    """Rotary swaps the halves by a signed permutation at ``HIGHEST``
    precision: exactly the split's (-x2, x1)."""
    x = jax.random.normal(jax.random.PRNGKey(2), (B, 8, 4, HD), jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    want = jnp.concatenate([-x2, x1], axis=-1)
    assert (jax.jit(layers._rotate_half)(x) == want).all()


def _implicit_call(q, k, v):
    pos = _pos()
    return attention(q, k, v, pos, pos, implicit_positions=True)


def test_one_device_mesh_keeps_the_kernel():
    mesh = jax.make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1],
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    with jax.set_mesh(mesh):
        assert _has_kernel(_implicit_call, *_qkv(4, 2)[:3])


@pytest.mark.multidevice
@pytest.mark.parametrize("mesh_shape", [(4, 1), (2, 2)])
def test_mesh_of_several_devices_keeps_the_xla_path(mesh_shape):
    """Under a mesh of several devices the eligible call traces no kernel
    and returns, to the bit, what the XLA path returns."""
    q, k, v, _ = _qkv(4, 2)
    q, k, v = (jnp.concatenate([x, x[::-1]]) for x in (q, k, v))
    pos = jnp.concatenate([_pos()] * 2)

    def xla(q, k, v):
        return attention(q, k, v, pos, pos)

    def implicit(q, k, v):
        return attention(q, k, v, pos, pos, implicit_positions=True)

    mesh = jax.make_mesh(mesh_shape, ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    with jax.set_mesh(mesh):
        args = [jax.device_put(x, NamedSharding(mesh, P("data")))
                for x in (q, k, v)]
        assert not _has_kernel(implicit, *args)
        got, want = jax.jit(implicit)(*args), jax.jit(xla)(*args)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.skipif(jax.device_count() >= 4,
                    reason="marked tests already run in this session")
def test_multidevice_subprocess_smoke(multidev_runner):
    """Tier-1 coverage of the marked tests: re-run them in a child
    process with a forced 4-device host platform."""
    proc = multidev_runner(
        ["-m", "multidevice", "tests/test_attention_kernel.py"])
    tail = (proc.stdout + proc.stderr)[-3000:]
    assert proc.returncode == 0, tail
    assert "passed" in proc.stdout, tail
    assert "skipped" not in proc.stdout, tail
