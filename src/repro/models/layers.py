"""Shared model primitives: norms, RoPE/M-RoPE, GQA attention (full,
query-chunked, windowed, cached), tapped dense layers for K-FAC stats.

Conventions
-----------
* Params are nested dicts of fp32 arrays; compute casts to ``cfg.dtype``.
* Every K-FAC-factored linear goes through :func:`dense`, which (a) adds
  the optional gradient *tap* (see core/kfac.py) and (b) records the
  input-side blocked Gram when stats collection is on.
* ``Ctx`` threads tap slices + collected stats through a scanned block.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import soi
from repro.dist.api import BATCH_AXES, DATA, MODEL, active_mesh, shard_hint
from repro.kernels import flash_attention

#: far-future sentinel position: the causal mask (q_pos >= kv_pos)
#: excludes cache columns carrying it. Lives here (the lowest layer that
#: knows about position tracks); repro.serve.pool re-exports it.
UNWRITTEN_POS = 2 ** 30


@dataclasses.dataclass
class Ctx:
    """Per-layer forward context (inside scan, taps/stats are the slices
    of the current layer).

    ``collect`` is False (off), True (record the input-side blocked
    Gram), or the string ``"cols"`` (record the raw blocked token
    columns — ``soi.blocked_tokens`` — whose Gram is the same statistic;
    the SMW rank-k refresh path needs the columns themselves)."""

    taps: Optional[Dict[str, jax.Array]] = None
    collect: bool = False
    stats: Dict[str, jax.Array] = dataclasses.field(default_factory=dict)
    soi_block: int = 1024

    def sub(self, taps, collect=None):
        return Ctx(taps=taps, collect=self.collect if collect is None
                   else collect, stats={}, soi_block=self.soi_block)


def cast(x: jax.Array, dtype) -> jax.Array:
    return x.astype(dtype) if x.dtype != dtype else x


def dense(x: jax.Array, w: jax.Array, name: str, ctx: Optional[Ctx] = None,
          bias: Optional[jax.Array] = None, stack_dims: int = 0,
          collect_gram: bool = True) -> jax.Array:
    """Tapped linear: ``y = x @ w (+ b) (+ tap[name])``.

    ``x``: (..., T, d_in). ``stack_dims`` leading dims of ``x`` are kept
    as factor-stack dims in the collected Gram (e.g. the expert dim of an
    MoE dispatch buffer); the rest are flattened as tokens.
    ``collect_gram=False`` skips the A-Gram for linears that share their
    input factor with a sibling (LinearSpec.share_a_with)."""
    dt = x.dtype
    y = jax.lax.dot_general(
        x, cast(w, dt), (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    if bias is not None:
        y = y + cast(bias, jnp.float32)
    if ctx is not None:
        if ctx.collect and collect_gram:
            a = x.astype(jnp.float32)
            a = a.reshape(a.shape[:stack_dims] + (-1, a.shape[-1]))
            ctx.stats[name] = (
                soi.blocked_tokens(a, ctx.soi_block)
                if ctx.collect == "cols"
                else soi.blocked_gram(a, ctx.soi_block))
        if ctx.taps is not None and name in ctx.taps:
            y = y + ctx.taps[name].reshape(y.shape)
    return y.astype(dt)


def dense_stacked(x: jax.Array, w: jax.Array, name: str,
                  ctx: Optional[Ctx] = None,
                  collect_gram: bool = True) -> jax.Array:
    """Batched tapped linear for stacked weights (e.g. MoE experts).

    ``x``: (S..., T, d_in), ``w``: (S..., d_in, d_out) with matching
    leading stack dims. Grams keep the stack dims."""
    dt = x.dtype
    y = jnp.einsum("...td,...df->...tf", x, cast(w, dt),
                   preferred_element_type=jnp.float32)
    if ctx is not None:
        if ctx.collect and collect_gram:
            xf = x.astype(jnp.float32)
            ctx.stats[name] = (
                soi.blocked_tokens(xf, ctx.soi_block)
                if ctx.collect == "cols"
                else soi.blocked_gram(xf, ctx.soi_block))
        if ctx.taps is not None and name in ctx.taps:
            y = y + ctx.taps[name].reshape(y.shape)
    return y.astype(dt)


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + cast(w, jnp.float32))
    return out.astype(dt)


def layer_norm(x: jax.Array, w: jax.Array, b: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps) * cast(w, jnp.float32) \
        + cast(b, jnp.float32)
    return out.astype(dt)


# ---------------------------------------------------------------------------
# RoPE / M-RoPE
# ---------------------------------------------------------------------------

def rope_freqs(hd: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               sections: Tuple[int, ...] = ()) -> jax.Array:
    """Rotary embedding.

    ``x``: (B, T, H, hd); ``positions``: (B, T) or (3, B, T) for M-RoPE
    (qwen2-vl), in which case ``sections`` gives the per-stream split of
    the hd/2 frequency channels (e.g. (16, 24, 24) for hd=128).

    The halves are swapped by a product with a signed permutation
    (:func:`_rotate_half`), which gives the values of splitting into
    half-width slices; on a TPU the slices' (hd/2)-wide minor dim is
    copied between layouts on its way to attention's head-major kernel.
    """
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)            # (hd/2,)
    if positions.ndim == 3 and sections:
        # M-RoPE: frequency channels are partitioned across the three
        # position streams (temporal, height, width).
        parts = []
        start = 0
        for s, sec in zip(range(3), sections):
            parts.append(positions[s][..., None] *
                         freqs[start:start + sec])
            start += sec
        ang = jnp.concatenate(parts, axis=-1)  # (B, T, hd/2)
    else:
        if positions.ndim == 3:
            positions = positions[0]
        ang = positions[..., None].astype(jnp.float32) * freqs
    sin = jnp.sin(ang)[:, :, None, :]
    cos = jnp.cos(ang)[:, :, None, :]
    xf = x.astype(jnp.float32)
    out = (xf * jnp.concatenate([cos, cos], axis=-1)
           + _rotate_half(xf) * jnp.concatenate([sin, sin], axis=-1))
    return out.astype(x.dtype)


def _rotate_half(x: jax.Array) -> jax.Array:
    """(x1, x2) -> (-x2, x1) along the last axis, as the product with a
    signed permutation: exact at ``HIGHEST`` precision."""
    h = x.shape[-1] // 2
    eye, zero = jnp.eye(h, dtype=x.dtype), jnp.zeros((h, h), x.dtype)
    r = jnp.block([[zero, eye], [-eye, zero]])
    return jnp.einsum("...d,de->...e", x, r,
                      precision=jax.lax.Precision.HIGHEST)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def _gqa_scores_to_out(q, k, v, mask, dt):
    """Dense-score attention for one (query-block, full-kv) pair.

    q: (B, T, Hkv, G, hd); k: (B, S, Hkv, hd); v: (B, S, Hkv, dv);
    mask: (B?, T, S) bool."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bthgd,bshd->bhgts", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask[:, None, None, :, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgts,bshe->bthge", p.astype(dt), v,
                   preferred_element_type=jnp.float32)
    return o.astype(dt)


def attention(q: jax.Array, k: jax.Array, v: jax.Array,
              q_pos: jax.Array, kv_pos: jax.Array,
              causal: bool = True, window: int = 0,
              chunk: int = 0, implicit_positions: bool = False
              ) -> jax.Array:
    """GQA attention with optional causality, sliding window, and
    query-chunking.

    q: (B, T, H, hd); k: (B, S, Hkv, hd); v: (B, S, Hkv, dv) (latent
    attention's value heads are narrower than its query/key heads; the
    scale is ``hd ** -0.5``); q_pos: (B, T) absolute positions; kv_pos:
    (B, S). Returns (B, T, H, dv).

    Two paths. Causal self-attention over the implicit positions
    ``arange(T)`` (``implicit_positions``: the caller's k/v are this
    call's own tokens and it passed no positions of its own), with no
    window, ``T == S`` a multiple of 128 and no mesh of several devices
    in scope, lowers on a TPU to the blocked online-softmax (flash)
    kernel of ``kernels/flash_attention``: each score tile stays in
    VMEM, blocks above the diagonal are skipped, and the backward
    recomputes the tiles from the saved logsumexp. Every other call, and
    every call lowered for another platform, takes the XLA path below:
    dense f32 scores, masked, softmaxed, cast to the value dtype for PV;
    queries longer than ``chunk`` are scanned in chunks, which bounds the
    score tensor at (chunk x S).
    """
    mesh = active_mesh()
    if (implicit_positions and causal and not window
            and flash_attention.supports(q.shape[1], k.shape[1])
            and (mesh is None or mesh.size == 1)):
        return jax.lax.platform_dependent(
            q, k, v, tpu=flash_attention.causal_flash_attention,
            default=lambda q, k, v: _xla_attention(
                q, k, v, q_pos, kv_pos, causal, window, chunk))
    return _xla_attention(q, k, v, q_pos, kv_pos, causal, window, chunk)


def _xla_attention(q, k, v, q_pos, kv_pos, causal, window, chunk):
    B, T, H, hd = q.shape
    S = k.shape[1]
    hkv, dv = k.shape[2], v.shape[-1]
    g = H // hkv
    dt = q.dtype
    qg = q.reshape(B, T, hkv, g, hd)

    def mask_for(qp):    # (B, t) -> (B, t, S)
        m = jnp.ones((B, qp.shape[1], S), bool)
        if causal:
            m &= qp[:, :, None] >= kv_pos[:, None, :]
        if window:
            m &= kv_pos[:, None, :] > qp[:, :, None] - window
        return m

    if chunk and T > chunk:
        # pad queries to a chunk multiple; pad rows carry q_pos = -1 so
        # the causal mask blanks them (uniform softmax over -1e30 rows
        # is finite; padded outputs are sliced away below)
        pad = (-T) % chunk
        Tp = T + pad
        if pad:
            qg = jnp.pad(qg, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
            q_pos = jnp.pad(q_pos, ((0, 0), (0, pad)),
                            constant_values=-1)
        nch = Tp // chunk
        qs = qg.reshape(B, nch, chunk, hkv, g, hd).transpose(
            1, 0, 2, 3, 4, 5)
        ps = q_pos.reshape(B, nch, chunk).transpose(1, 0, 2)

        def body(_, qc_pc):
            qc, pc = qc_pc
            return None, _gqa_scores_to_out(qc, k, v, mask_for(pc), dt)

        # nested remat: don't save per-chunk score/prob tensors for the
        # backward pass (they are the largest train-time activations);
        # recompute them — the layer-level remat already recomputes the
        # forward, so this only changes what the chunk scan *stacks*
        # (EXPERIMENTS.md §Perf 1.7)
        body = jax.checkpoint(body)
        _, outs = jax.lax.scan(body, None, (qs, ps))
        out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(
            B, Tp, hkv, g, dv)[:, :T]
    else:
        out = _gqa_scores_to_out(qg, k, v, mask_for(q_pos), dt)
    return out.reshape(B, T, H, dv)


def kv_cache_update(cache_k, cache_v, k, v, idx):
    """Insert k/v (B, t, Hkv, hd) at position idx into (B, S, Hkv, hd).

    ``idx`` is either a scalar (all rows write the same column — the
    static decode path) or a (B,) vector of per-row columns with t == 1
    (the continuous-batching slot pool, where every slot sits at its own
    sequence position). Vector rows with ``idx >= S`` write nothing."""
    idx = jnp.asarray(idx)
    if idx.ndim == 1:
        # per-row scatter (in-place under donation): O(B * Hkv * hd)
        # per step, not a full-cache select
        rows = jnp.arange(cache_k.shape[0])
        ck = cache_k.at[rows, idx].set(
            k[:, 0].astype(cache_k.dtype), mode="drop")
        cv = cache_v.at[rows, idx].set(
            v[:, 0].astype(cache_v.dtype), mode="drop")
        return ck, cv
    ck = jax.lax.dynamic_update_slice(cache_k, k.astype(cache_k.dtype),
                                      (0, idx, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache_v, v.astype(cache_v.dtype),
                                      (0, idx, 0, 0))
    return ck, cv


def pos_cache_update(cache_pos, q_pos, idx):
    """Insert positions (B, t) at column idx into the (B, S) pos track,
    with the same scalar/vector ``idx`` contract as kv_cache_update."""
    idx = jnp.asarray(idx)
    if idx.ndim == 1:
        rows = jnp.arange(cache_pos.shape[0])
        return cache_pos.at[rows, idx].set(
            q_pos[:, 0].astype(cache_pos.dtype), mode="drop")
    return jax.lax.dynamic_update_slice(
        cache_pos, q_pos.astype(cache_pos.dtype), (0, idx))


# ---------------------------------------------------------------------------
# Paged KV (block-table indirection for the serving pool)
# ---------------------------------------------------------------------------
#
# The paged pool stores KV in fixed-size position blocks:
#   k/v : (n_blocks, block_len, Hkv, hd)     pos : (n_blocks, block_len)
# and each batch row owns a block table (B, nbps) of physical block ids,
# where table entry j covers absolute positions [j*bl, (j+1)*bl).  The
# sentinel id ``n_blocks`` means "unmapped": reads fill pos with
# UNWRITTEN_POS (masked by the causal mask, exactly like unwritten slot
# columns) and writes drop.  Virtual column c of the gathered cache is
# absolute position c — the same column ordering as the dense slot
# layout, which is what makes paged decode bitwise the slot decode.

def paged_kv_read(cache_k, cache_v, cache_pos, table):
    """Gather per-row virtual KV rows from the block pool.

    cache_k/v: (n_blocks, bl, Hkv, hd); cache_pos: (n_blocks, bl);
    table: (B, nbps) int32 with ``n_blocks`` as the unmapped sentinel.
    Returns k/v (B, nbps*bl, Hkv, hd) and kv_pos (B, nbps*bl)."""
    B, nbps = table.shape
    bl = cache_k.shape[1]
    kg = jnp.take(cache_k, table, axis=0, mode="fill", fill_value=0)
    vg = jnp.take(cache_v, table, axis=0, mode="fill", fill_value=0)
    pg = jnp.take(cache_pos, table, axis=0, mode="fill",
                  fill_value=UNWRITTEN_POS)
    kg = kg.reshape(B, nbps * bl, *cache_k.shape[2:])
    vg = vg.reshape(B, nbps * bl, *cache_v.shape[2:])
    return kg, vg, pg.reshape(B, nbps * bl)


def paged_kv_write(cache_k, cache_v, cache_pos, table, k, v, q_pos, idx):
    """Per-row decode write into the block pool (t == 1).

    k/v: (B, 1, Hkv, hd); q_pos: (B, 1); idx: (B,) absolute positions.
    Rows whose table entry for ``idx`` is unmapped (or whose idx is past
    the table) write nothing — mirroring the ``idx >= S`` drop of the
    dense slot path."""
    n_blocks, bl = cache_k.shape[0], cache_k.shape[1]
    nbps = table.shape[1]
    rows = jnp.arange(table.shape[0])
    col = idx // bl
    blk = jnp.where(col < nbps,
                    table[rows, jnp.minimum(col, nbps - 1)], n_blocks)
    off = idx % bl
    ck = cache_k.at[blk, off].set(
        k[:, 0].astype(cache_k.dtype), mode="drop")
    cv = cache_v.at[blk, off].set(
        v[:, 0].astype(cache_v.dtype), mode="drop")
    cp = cache_pos.at[blk, off].set(
        q_pos[:, 0].astype(cache_pos.dtype), mode="drop")
    return ck, cv, cp


# ---------------------------------------------------------------------------
# Activations / misc
# ---------------------------------------------------------------------------

def swiglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    return jax.nn.silu(gate.astype(jnp.float32)).astype(gate.dtype) * up


def gelu(x: jax.Array) -> jax.Array:
    return jax.nn.gelu(x.astype(jnp.float32)).astype(x.dtype)


def causal_conv1d(x: jax.Array, w: jax.Array,
                  b: Optional[jax.Array] = None,
                  state: Optional[jax.Array] = None,
                  length: Optional[jax.Array] = None):
    """Depthwise causal conv along time. x: (B, T, C); w: (C, W).

    If ``state`` (B, W-1, C) is given (decode), it is the left context and
    the updated state is returned alongside.  ``length`` (B,) marks the
    per-row valid prefix of a right-padded prefill: the returned state is
    then the window ending at position ``length-1`` (column ``length-1``
    of the padded input) rather than at the padded tail — padding past
    ``length`` never leaks into decode.  The conv *outputs* need no
    masking: causality means columns < length only see columns < length.
    """
    W = w.shape[-1]
    if state is not None:
        xin = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    else:
        xin = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    if state is None and length is None:
        new_state = None                      # training: no state carried
    elif W > 1:
        if length is not None:
            # xin column (length + i) holds position (length - W + 1 + i):
            # the left context of position `length` — the first decode
            # step after a prefill of `length` valid tokens.
            cols = length[:, None] + jnp.arange(W - 1)[None, :]
            new_state = jnp.take_along_axis(
                xin, cols[:, :, None], axis=1).astype(
                    state.dtype if state is not None else x.dtype)
        else:
            new_state = xin[:, -(W - 1):, :]
    else:
        new_state = state
    out = jnp.zeros_like(x, dtype=jnp.float32)
    T = x.shape[1]
    for i in range(W):
        out = out + xin[:, i:i + T, :].astype(jnp.float32) \
            * w[:, i].astype(jnp.float32)
    if b is not None:
        out = out + b.astype(jnp.float32)
    return out.astype(x.dtype), new_state


def shard_tokens(x: jax.Array) -> jax.Array:
    """Hint: batch over (pod, data)."""
    return shard_hint(x, BATCH_AXES)


def shard_acts(x: jax.Array) -> jax.Array:
    """Hint: (B, T, D) activations — batch over (pod,data), D over model."""
    return shard_hint(x, BATCH_AXES, None, MODEL)
