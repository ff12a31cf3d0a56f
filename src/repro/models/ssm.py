"""Mamba-1 selective SSM mixer (falcon-mamba-7b arch).

Training/prefill use a work-efficient associative scan (log-depth on TPU,
``jax.lax.associative_scan``); decode is the O(1) recurrent step with
carried (h, conv) state. The diagonal recurrence params (A_log, D, conv,
dt_bias) are elementwise — no Kronecker structure — so they take the
first-order path; all projections are K-FAC-factored (DESIGN.md §4).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.dist.api import BATCH_AXES, MODEL, shard_hint
from repro.models.layers import Ctx, causal_conv1d, dense


def init_mamba(cfg, key) -> Dict:
    d, di, n, dr = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.dt_rank_
    ks = jax.random.split(key, 5)
    s = d ** -0.5
    return {
        "in_proj": jax.random.normal(ks[0], (d, 2 * di), jnp.float32) * s,
        "conv_w": jax.random.normal(ks[1], (di, cfg.ssm_conv),
                                    jnp.float32) * 0.1,
        "conv_b": jnp.zeros((di,), jnp.float32),
        "x_proj": jax.random.normal(ks[2], (di, dr + 2 * n),
                                    jnp.float32) * di ** -0.5,
        "dt_proj": jax.random.normal(ks[3], (dr, di),
                                     jnp.float32) * dr ** -0.5,
        "dt_bias": jnp.log(jnp.expm1(
            jnp.full((di,), 0.01, jnp.float32))),  # softplus^-1(0.01)
        "A_log": jnp.log(jnp.broadcast_to(
            jnp.arange(1, n + 1, dtype=jnp.float32), (di, n)) + 0.0),
        "D": jnp.ones((di,), jnp.float32),
        "out_proj": jax.random.normal(ks[4], (di, d), jnp.float32)
        * di ** -0.5,
    }


def _ssm_params(cfg, p, xc, prefix, ctx):
    """Shared projection math: returns (dt, B, C) from conv output."""
    n, dr = cfg.ssm_state, cfg.dt_rank_
    x_dbl = dense(xc, p["x_proj"], f"{prefix}/x_proj", ctx)
    dt_r, b, c = jnp.split(x_dbl, [dr, dr + n], axis=-1)
    dt = dense(dt_r, p["dt_proj"], f"{prefix}/dt_proj", ctx)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    return dt, b.astype(jnp.float32), c.astype(jnp.float32)


@jax.named_scope("ssm")
def mamba_mixer(cfg, p: Dict, x: jax.Array, ctx: Optional[Ctx],
                prefix: str,
                state: Optional[Tuple[jax.Array, jax.Array]] = None,
                length: Optional[jax.Array] = None):
    """x: (B, T, D). ``state``: (h (B, di, n), conv (B, W-1, di)) for
    decode. ``length`` (B,) marks the valid prefix of a right-padded
    prefill: the returned state is then the recurrent state *at*
    position length-1, not at the padded tail (causality means the scan
    values at columns < length are pad-independent; only the boundary
    gather needs care). Returns (y, new_state)."""
    B, T, D = x.shape
    di, n = cfg.d_inner, cfg.ssm_state

    xz = dense(x, p["in_proj"], f"{prefix}/in_proj", ctx)
    xin, z = jnp.split(xz, 2, axis=-1)
    xin = shard_hint(xin, BATCH_AXES, None, MODEL)

    h0 = conv0 = None
    if state is not None:
        h0, conv0 = state
    xc, conv1 = causal_conv1d(xin, p["conv_w"], p["conv_b"], state=conv0,
                              length=length if T > 1 else None)
    xc = jax.nn.silu(xc.astype(jnp.float32)).astype(x.dtype)

    dt, bmat, cmat = _ssm_params(cfg, p, xc, prefix, ctx)
    a = -jnp.exp(p["A_log"].astype(jnp.float32))        # (di, n)
    # discretize: (B, T, di, n)
    ab = jnp.exp(dt[..., None] * a)
    bx = (dt * xc.astype(jnp.float32))[..., None] * bmat[:, :, None, :]
    ab = shard_hint(ab, BATCH_AXES, None, MODEL, None)
    bx = shard_hint(bx, BATCH_AXES, None, MODEL, None)

    if T == 1 and h0 is not None:
        h = ab[:, 0] * h0 + bx[:, 0]                    # (B, di, n)
        hs = h[:, None]
        new_h = h
    else:
        def comb(l, r):
            al, bl = l
            ar, br = r
            return al * ar, bl * ar + br

        if h0 is not None:
            bx = bx.at[:, 0].add(ab[:, 0] * h0)
        _, hs = jax.lax.associative_scan(comb, (ab, bx), axis=1)
        if length is not None:
            new_h = jnp.take_along_axis(
                hs, (length - 1)[:, None, None, None], axis=1)[:, 0]
        else:
            new_h = hs[:, -1]

    y = jnp.einsum("btdn,btn->btd", hs, cmat,
                   preferred_element_type=jnp.float32)
    y = y + p["D"].astype(jnp.float32) * xc.astype(jnp.float32)
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    out = dense(y, p["out_proj"], f"{prefix}/out_proj", ctx)
    return out, (new_h, conv1)


def init_mamba_state(cfg, batch: int, dtype=jnp.float32):
    di, n, w = cfg.d_inner, cfg.ssm_state, cfg.ssm_conv
    return (jnp.zeros((batch, di, n), dtype),
            jnp.zeros((batch, w - 1, di), dtype))
