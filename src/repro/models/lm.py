"""Generic LM-family model: dense / MoE / SSM / hybrid / VLM backbones.

One scanned-layer decoder with per-family mixer blocks:

  dense, vlm : global attention + SwiGLU MLP
  moe        : global attention + top-k MoE FFN (capacity); or, with
               ``cfg.dropless`` (DeepSeek-V3 / Moonlight), latent
               attention (MLA) + shared experts + the held share of the
               routed experts, after ``cfg.n_dense_layers`` leading
               MLA + SwiGLU layers (a stack of their own, ``dense_layers``)
  ssm        : Mamba-1 mixer only (no MLP, d_ff = 0)
  hybrid     : RecurrentGemma pattern units (rec, rec, local-attn), each
               sub-layer followed by a SwiGLU MLP

All layers live under ``jax.lax.scan`` (uniform) or a scanned
pattern-unit + explicit tail (hybrid) so HLO size is one-layer-sized.
Backward memory is bounded by per-layer remat (``cfg.remat``).

K-FAC integration: every factored linear is a ``layers.dense`` /
``dense_stacked`` call with a path-accurate name; taps enter via scan
xs, activation Grams leave via scan ys (see core/kfac.py).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.soi import LinearSpec
from repro.dist.api import (
    BATCH_AXES,
    MODEL,
    bwd_psum_if_bound,
    psum_if_bound,
    shard_hint,
)
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    UNWRITTEN_POS,
    Ctx,
    apply_rope,
    attention,
    cast,
    dense,
    kv_cache_update,
    paged_kv_read,
    paged_kv_write,
    pos_cache_update,
    rms_norm,
    shard_acts,
    swiglu,
)

# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_attn(cfg, key) -> Dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    s = d ** -0.5
    p = {
        "wq": jax.random.normal(ks[0], (d, h * hd), jnp.float32) * s,
        "wk": jax.random.normal(ks[1], (d, kv * hd), jnp.float32) * s,
        "wv": jax.random.normal(ks[2], (d, kv * hd), jnp.float32) * s,
        "wo": jax.random.normal(ks[3], (h * hd, d), jnp.float32)
        * (h * hd) ** -0.5,
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), jnp.float32)
        p["bk"] = jnp.zeros((kv * hd,), jnp.float32)
        p["bv"] = jnp.zeros((kv * hd,), jnp.float32)
    return p


def _init_mla(cfg, key) -> Dict:
    """Latent attention in its training form: q straight from x; the
    joint ``wkv_a`` gives the latent (``kv_lora_rank``) and the shared
    rope key; ``wkv_b`` lifts the normed latent to per-head nope keys and
    values."""
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    kv_b = h * (cfg.qk_nope_dim + cfg.v_head_dim)
    ks = jax.random.split(key, 4)
    return {
        "wq": jax.random.normal(ks[0], (d, h * cfg.qk_dim), jnp.float32)
        * d ** -0.5,
        "wkv_a": jax.random.normal(ks[1], (d, r + cfg.qk_rope_dim),
                                   jnp.float32) * d ** -0.5,
        "kv_norm": jnp.zeros((r,), jnp.float32),
        "wkv_b": jax.random.normal(ks[2], (r, kv_b), jnp.float32)
        * r ** -0.5,
        "wo": jax.random.normal(ks[3], (h * cfg.v_head_dim, d),
                                jnp.float32) * (h * cfg.v_head_dim) ** -0.5,
    }


def _init_mlp(cfg, key, width=None) -> Dict:
    d, f = cfg.d_model, width or cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "wg": jax.random.normal(ks[0], (d, f), jnp.float32) * d ** -0.5,
        "wu": jax.random.normal(ks[1], (d, f), jnp.float32) * d ** -0.5,
        "wd": jax.random.normal(ks[2], (f, d), jnp.float32) * f ** -0.5,
    }


def _init_layer(cfg, kind: str, key) -> Dict:
    d = cfg.d_model
    ks = jax.random.split(key, 3)
    p: Dict[str, Any] = {"ln1": jnp.zeros((d,), jnp.float32)}
    if cfg.mla and kind in ("attn", "moe"):
        p["mla"] = _init_mla(cfg, ks[0])
    elif kind in ("attn", "local", "moe"):
        p["attn"] = _init_attn(cfg, ks[0])
    if kind in ("attn", "local"):
        p["ln2"] = jnp.zeros((d,), jnp.float32)
        p["mlp"] = _init_mlp(cfg, ks[1])
    elif kind == "moe" and cfg.dropless:
        p["ln2"] = jnp.zeros((d,), jnp.float32)
        p["moe"] = moe_mod.init_share(cfg, ks[1])
        p["shared"] = _init_mlp(cfg, ks[2],
                                cfg.n_shared_experts * cfg.d_expert_)
    elif kind == "moe":
        p["ln2"] = jnp.zeros((d,), jnp.float32)
        p["moe"] = moe_mod.init_moe(cfg, ks[1])
    elif kind == "mamba":
        p["mamba"] = ssm_mod.init_mamba(cfg, ks[0])
    elif kind == "rec":
        p["rec"] = rglru_mod.init_rglru(cfg, ks[0])
        p["ln2"] = jnp.zeros((d,), jnp.float32)
        p["mlp"] = _init_mlp(cfg, ks[1])
    else:
        raise ValueError(kind)
    return p


def layer_plan(cfg) -> Tuple[str, ...]:
    """Per-layer kind sequence."""
    if cfg.family in ("dense", "vlm"):
        return ("attn",) * cfg.n_layers
    if cfg.family == "moe":
        return ("attn",) * cfg.n_dense_layers \
            + ("moe",) * (cfg.n_layers - cfg.n_dense_layers)
    if cfg.family == "ssm":
        return ("mamba",) * cfg.n_layers
    if cfg.family == "hybrid":
        return tuple(cfg.pattern[i % len(cfg.pattern)]
                     for i in range(cfg.n_layers))
    raise ValueError(cfg.family)


def stacks(cfg) -> Tuple[Tuple[str, str, int], ...]:
    """(params key, layer kind, depth) of each scanned stack of a
    non-hybrid model, in order: the leading dense layers
    (``dense_layers``), then ``layers``."""
    plan = layer_plan(cfg)
    n_dense = cfg.n_dense_layers if cfg.family == "moe" else 0
    lead = (("dense_layers", "attn", n_dense),) if n_dense else ()
    return lead + (("layers", plan[-1], len(plan) - n_dense),)


def _hybrid_split(cfg) -> Tuple[int, Tuple[str, ...]]:
    unit = tuple(cfg.pattern)
    n_units = cfg.n_layers // len(unit)
    tail = tuple(unit[: cfg.n_layers % len(unit)])
    return n_units, tail


def init(cfg, key) -> Dict:
    ks = jax.random.split(key, 8)
    d, v = cfg.d_model, cfg.vocab
    params: Dict[str, Any] = {
        "embed": jax.random.normal(ks[0], (v, d), jnp.float32) * 0.02,
        "final_norm": jnp.zeros((d,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(
            ks[1], (d, v), jnp.float32) * d ** -0.5
    if cfg.family == "vlm" and cfg.vision_dim:
        params["img_proj"] = jax.random.normal(
            ks[2], (cfg.vision_dim, d), jnp.float32) * cfg.vision_dim ** -0.5

    if cfg.family == "hybrid":
        n_units, tail = _hybrid_split(cfg)
        unit_keys = jax.random.split(ks[3], n_units)

        def one_unit(k):
            kk = jax.random.split(k, len(cfg.pattern))
            return {f"sub{i}": _init_layer(cfg, kind, kk[i])
                    for i, kind in enumerate(cfg.pattern)}

        params["units"] = jax.vmap(one_unit)(unit_keys)
        tk = jax.random.split(ks[4], max(len(tail), 1))
        params["tail"] = {f"sub{i}": _init_layer(cfg, kind, tk[i])
                          for i, kind in enumerate(tail)}
    else:
        for prefix, kind, n in stacks(cfg):
            layer_keys = jax.random.split(
                ks[3] if prefix == "layers" else ks[5], n)
            params[prefix] = jax.vmap(
                functools.partial(_init_layer, cfg, kind))(layer_keys)
    return params


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

@jax.named_scope("attn")
def _attn_block(cfg, p, x, positions, ctx, prefix, *, window=0,
                cache=None, idx=None, mrope=False, table=None,
                implicit_positions=False):
    """Pre-norm attention sub-layer. cache: dict(k, v, pos) slices for
    this layer or None. ``table`` (B, nbps) switches the cache to the
    block-paged layout (repro.serve.paged): k/v/pos leaves are
    (n_blocks, block_len, ...) pools indirected per row through the
    table. ``implicit_positions``: ``positions`` is the ``arange`` that
    :func:`forward` builds, which lets attention without a cache take
    the flash kernel (``layers.attention``). Returns (x + attn_out,
    new_cache)."""
    B, T, D = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    xin = rms_norm(x, p["ln1"], cfg.norm_eps)
    if p["attn"]["wq"].shape[-1] < h * hd:
        # model-sliced q/k/v ahead: reduce the partial input-cotangents
        # the slices produce back to the true gradient (megatron `f`)
        xin = bwd_psum_if_bound(xin, MODEL)
    q = dense(xin, p["attn"]["wq"], f"{prefix}/attn/wq", ctx,
              bias=p["attn"].get("bq"))
    k = dense(xin, p["attn"]["wk"], f"{prefix}/attn/wk", ctx,
              bias=p["attn"].get("bk"), collect_gram=False)
    v = dense(xin, p["attn"]["wv"], f"{prefix}/attn/wv", ctx,
              bias=p["attn"].get("bv"), collect_gram=False)
    # Head counts are inferred from the projection outputs, not cfg:
    # inside the manual (pipeline × model) stage program the weights
    # arrive pre-sliced over the model axis (megatron column-parallel),
    # so each shard sees h_loc = h/mp query heads. Under GSPMD or with
    # model=1 the shapes are full and h_loc == h.
    h_loc, kv_loc = q.shape[-1] // hd, k.shape[-1] // hd
    q = q.reshape(B, T, h_loc, hd)
    k = k.reshape(B, T, kv_loc, hd)
    v = v.reshape(B, T, kv_loc, hd)
    sections = cfg.mrope_sections if mrope else ()
    q = apply_rope(q, positions, cfg.rope_theta, sections)
    k = apply_rope(k, positions, cfg.rope_theta, sections)
    q = shard_hint(q, BATCH_AXES, None, MODEL, None)
    k = shard_hint(k, BATCH_AXES, None, MODEL, None)

    q_pos = positions[0] if positions.ndim == 3 else positions
    new_cache = None
    if cache is not None and table is not None:
        # block-paged decode: per-row scatter into the block pool, then a
        # table-gather back to the virtual (B, nbps*bl) cache whose
        # column c is absolute position c — the same column ordering as
        # the dense slot layout, so attention is bitwise the slot path.
        if T != 1:
            raise NotImplementedError(
                "paged cache is decode-only (T == 1); prefill runs on a "
                "dense row and is scattered in by write_slot_paged")
        if window:
            raise NotImplementedError(
                "paged cache does not support windowed rings")
        ck, cv, cpos = paged_kv_write(
            cache["k"], cache["v"], cache["pos"], table, k, v, q_pos, idx)
        k_all, v_all, kv_pos = paged_kv_read(ck, cv, cpos, table)
        k_all = k_all.astype(q.dtype)
        v_all = v_all.astype(q.dtype)
        new_cache = {"k": ck, "v": cv, "pos": cpos}
    elif cache is not None and T > 1 and window and T > cache["k"].shape[1]:
        # Windowed prefill longer than the ring: attend in-sequence, then
        # store only the last S tokens rolled to their ring slots
        # (invariant: pos p lives at slot p % S).
        S = cache["k"].shape[1]
        k_all, v_all, kv_pos = k, v, q_pos
        shift = (idx + T) % S
        ck = jnp.roll(k[:, -S:].astype(cache["k"].dtype), shift, axis=1)
        cv = jnp.roll(v[:, -S:].astype(cache["v"].dtype), shift, axis=1)
        cpos = jnp.roll(q_pos[:, -S:].astype(jnp.int32), shift, axis=1)
        new_cache = {"k": ck, "v": cv, "pos": cpos}
    elif cache is not None:
        # write this step's k/v at slot idx (ring-buffered for windows;
        # idx may be a (B,) per-slot vector on the serving pool path)
        S = cache["k"].shape[1]
        slot = idx % S if window else idx
        ck, cv = kv_cache_update(cache["k"], cache["v"], k, v, slot)
        cpos = pos_cache_update(cache["pos"], q_pos, slot)
        kv_pos = cpos
        k_all, v_all = ck.astype(q.dtype), cv.astype(q.dtype)
        new_cache = {"k": ck, "v": cv, "pos": cpos}
    else:
        k_all, v_all = k, v
        kv_pos = q_pos
    out = attention(q, k_all, v_all, q_pos, kv_pos, causal=True,
                    window=window,
                    chunk=cfg.attn_chunk if T > cfg.attn_chunk else 0,
                    implicit_positions=implicit_positions)
    out = out.reshape(B, T, h_loc * hd)
    out = dense(out, p["attn"]["wo"], f"{prefix}/attn/wo", ctx)
    if h_loc < h:
        # row-parallel wo on a head slice: each model shard holds a
        # partial sum of the output projection
        out = psum_if_bound(out, MODEL)
    return x + shard_acts(out), new_cache


@jax.named_scope("mla")
def _mla_block(cfg, p, x, positions, ctx, prefix, *,
               implicit_positions=False):
    """Pre-norm latent attention (DeepSeek-V2/V3 MLA) in its training
    (non-absorbed) form: the nope keys and the values are lifted from
    the normed latent per head; the rope key is one head shared by all.
    Query/key heads are ``qk_nope_dim + qk_rope_dim`` wide, value heads
    ``v_head_dim``; the score scale is the query/key width's. The rope
    channels rotate by the repo's half split (``apply_rope``): the
    published code de-interleaves them first, which is this model with
    the rope columns of ``wq`` and ``wkv_a`` permuted."""
    B, T, _ = x.shape
    h, r = cfg.n_heads, cfg.kv_lora_rank
    nope, rope, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    pm = p["mla"]
    xin = rms_norm(x, p["ln1"], cfg.norm_eps)
    q = dense(xin, pm["wq"], f"{prefix}/mla/wq", ctx)
    kv_a = dense(xin, pm["wkv_a"], f"{prefix}/mla/wkv_a", ctx,
                 collect_gram=False)
    # the published latent norm keeps its module default eps, 1e-6
    c_kv = rms_norm(kv_a[..., :r], pm["kv_norm"], 1e-6)
    kv = dense(c_kv, pm["wkv_b"], f"{prefix}/mla/wkv_b", ctx)
    q = q.reshape(B, T, h, nope + rope)
    kv = kv.reshape(B, T, h, nope + dv)
    q_pe = apply_rope(q[..., nope:], positions, cfg.rope_theta)
    k_pe = apply_rope(kv_a[..., r:].reshape(B, T, 1, rope), positions,
                      cfg.rope_theta)
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (B, T, h, rope))], axis=-1)
    out = attention(q, k, kv[..., nope:], positions, positions,
                    causal=True,
                    chunk=cfg.attn_chunk if T > cfg.attn_chunk else 0,
                    implicit_positions=implicit_positions)
    out = dense(out.reshape(B, T, h * dv), pm["wo"], f"{prefix}/mla/wo",
                ctx)
    return x + shard_acts(out)


def _ffn(cfg, p, xin, ctx, prefix, width):
    """SwiGLU MLP ``p`` of ``width`` on the normed ``xin``."""
    if p["wg"].shape[-1] < width:
        xin = bwd_psum_if_bound(xin, MODEL)
    g = dense(xin, p["wg"], f"{prefix}/wg", ctx)
    u = dense(xin, p["wu"], f"{prefix}/wu", ctx, collect_gram=False)
    f_loc = g.shape[-1]           # < width when wg/wu arrive model-sliced
    hidden = swiglu(g, u)
    hidden = shard_hint(hidden, BATCH_AXES, None, MODEL)
    out = dense(hidden, p["wd"], f"{prefix}/wd", ctx)
    if f_loc < width:
        out = psum_if_bound(out, MODEL)
    return shard_acts(out)


@jax.named_scope("mlp")
def _mlp_block(cfg, p, x, ctx, prefix):
    xin = rms_norm(x, p["ln2"], cfg.norm_eps)
    return x + _ffn(cfg, p["mlp"], xin, ctx, f"{prefix}/mlp", cfg.d_ff)


def _share_moe_block(cfg, p, x, ctx, prefix):
    """Shared experts (one SwiGLU MLP of ``n_shared_experts`` expert
    widths, scope ``mlp``) plus the held share of the routed experts
    (``moe.share_ffn``) on the same normed input."""
    xin = rms_norm(x, p["ln2"], cfg.norm_eps)
    routed = moe_mod.share_ffn(cfg, p["moe"], xin, ctx, f"{prefix}/moe")
    with jax.named_scope("mlp"):
        shared = _ffn(cfg, p["shared"], xin, ctx, f"{prefix}/shared",
                      cfg.n_shared_experts * cfg.d_expert_)
    return x + (routed + shared)


def _layer_apply(cfg, kind, p, x, positions, ctx, prefix, cache=None,
                 idx=None, table=None, state_len=None,
                 implicit_positions=False):
    """One decoder layer of the given kind. Returns (x, new_cache).

    ``state_len`` (B,) is the per-row valid prefix of a right-padded
    prefill: recurrent mixers gather their carried state at position
    state_len-1 instead of the padded tail (attention needs no such care
    — unwritten columns carry UNWRITTEN_POS and are mask-excluded)."""
    if cfg.mla and kind in ("attn", "moe"):
        x = _mla_block(cfg, p, x, positions, ctx, prefix,
                       implicit_positions=implicit_positions)
        if kind == "attn":
            return _mlp_block(cfg, p, x, ctx, prefix), None
    if kind == "moe" and cfg.dropless:
        return _share_moe_block(cfg, p, x, ctx, prefix), None
    if kind in ("attn", "local"):
        window = cfg.window if kind == "local" else 0
        x, nc = _attn_block(cfg, p, x, positions, ctx, prefix,
                            window=window, cache=cache, idx=idx,
                            mrope=(cfg.family == "vlm"), table=table,
                            implicit_positions=implicit_positions)
        x = _mlp_block(cfg, p, x, ctx, prefix)
        return x, nc
    if kind == "moe":
        x, nc = _attn_block(cfg, p, x, positions, ctx, prefix,
                            cache=cache, idx=idx, table=table,
                            implicit_positions=implicit_positions)
        xin = rms_norm(x, p["ln2"], cfg.norm_eps)
        x = x + moe_mod.moe_ffn(cfg, p["moe"], xin, ctx, f"{prefix}/moe")
        return x, nc
    if kind == "mamba":
        xin = rms_norm(x, p["ln1"], cfg.norm_eps)
        y, nstate = ssm_mod.mamba_mixer(cfg, p["mamba"], xin, ctx,
                                        f"{prefix}/mamba", state=cache,
                                        length=state_len)
        return x + y, nstate
    if kind == "rec":
        xin = rms_norm(x, p["ln1"], cfg.norm_eps)
        y, nstate = rglru_mod.rglru_mixer(cfg, p["rec"], xin, ctx,
                                          f"{prefix}/rec", state=cache,
                                          length=state_len)
        x = x + y
        x = _mlp_block(cfg, p, x, ctx, prefix)
        return x, nstate
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _embed(cfg, params, batch, positions):
    tokens = batch["tokens"]
    dt = jnp.dtype(cfg.dtype)
    x = cast(params["embed"], dt)[tokens]
    if cfg.family == "vlm" and "img_embeds" in batch:
        # stubbed vision frontend (assignment): precomputed patch embeds
        # projected into the first n_img token slots
        img = jax.lax.dot_general(
            batch["img_embeds"].astype(dt), cast(params["img_proj"], dt),
            (((2,), (0,)), ((), ())), preferred_element_type=jnp.float32
        ).astype(dt)
        n_img = img.shape[1]
        T = x.shape[1]
        img_pad = jnp.pad(img, ((0, 0), (0, T - n_img), (0, 0)))
        x = x + img_pad
    return shard_acts(x)


@jax.named_scope("head")
def _logits(cfg, params, x):
    dt = x.dtype
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    # pad vocab to a shardable multiple of 128 (standard practice: an
    # odd vocab like whisper's 51865 otherwise forces replicated
    # logits, the largest activation in the model); padded columns are
    # masked to -1e30 so loss/argmax semantics are unchanged
    v = head.shape[-1]
    vpad = (-v) % 128
    if vpad:
        head = jnp.pad(head, ((0, 0), (0, vpad)))
    logits = jax.lax.dot_general(
        x, cast(head, dt), (((2,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    if vpad:
        mask = jnp.where(jnp.arange(v + vpad) < v, 0.0, -1e30)
        logits = logits + mask
    return shard_hint(logits, BATCH_AXES, None, MODEL)


def _scan_layers(cfg, params, x, positions, taps, collect, cache, idx,
                 train, table=None, state_len=None,
                 implicit_positions=False):
    """Run all layers; returns (x, stats, new_cache)."""
    stats_out: Dict[str, jax.Array] = {}

    def run_seq(prefix, kind, stacked, x, cache_tree):
        """Scan over the stacked layers of uniform ``kind``."""

        def body(xcur, xs):
            p_l, taps_l, cache_l = xs
            ctx = Ctx(taps=taps_l or None, collect=collect,
                      soi_block=cfg.soi_block)
            xnew, ncache = _layer_apply(
                cfg, kind, p_l, xcur, positions, ctx, prefix,
                cache=cache_l, idx=idx, table=table, state_len=state_len,
                implicit_positions=implicit_positions)
            if cache_l is None:
                ncache = None     # train: don't stack states as ys
            return xnew, (ctx.stats, ncache)

        fn = body
        if train and cfg.remat:
            fn = jax.checkpoint(body)
        taps_xs = {k: v for k, v in (taps or {}).items()
                   if k.startswith(prefix + "/")}
        x, (stats, ncache) = jax.lax.scan(
            fn, x, (stacked, taps_xs, cache_tree))
        stats_out.update(stats)
        return x, ncache

    new_cache = None
    if cfg.family == "hybrid":
        n_units, tail = _hybrid_split(cfg)
        sub_caches = (cache or {}).get("units") if cache else None
        tail_caches = (cache or {}).get("tail") if cache else None

        def body(xcur, xs):
            p_u, taps_u, cache_u = xs
            ncaches = {}
            stats = {}
            for i, kind in enumerate(cfg.pattern):
                ctx = Ctx(taps=taps_u or None, collect=collect,
                          soi_block=cfg.soi_block)
                c_i = cache_u.get(f"sub{i}") if cache_u else None
                xcur, nc = _layer_apply(
                    cfg, kind, p_u[f"sub{i}"], xcur, positions, ctx,
                    f"units/sub{i}", cache=c_i, idx=idx,
                    state_len=state_len,
                    implicit_positions=implicit_positions)
                stats.update(ctx.stats)
                if nc is not None:
                    ncaches[f"sub{i}"] = nc
            return xcur, (stats, ncaches)

        fn = jax.checkpoint(body) if (train and cfg.remat) else body
        taps_xs = {k: v for k, v in (taps or {}).items()
                   if k.startswith("units/")}
        x, (stats, ncache_units) = jax.lax.scan(
            fn, x, (params["units"], taps_xs, sub_caches))
        stats_out.update(stats)

        ncache_tail = {}
        for i, kind in enumerate(tail):
            ctx = Ctx(taps=taps or None, collect=collect,
                      soi_block=cfg.soi_block)
            c_i = tail_caches.get(f"sub{i}") if tail_caches else None
            x, nc = _layer_apply(cfg, kind, params["tail"][f"sub{i}"], x,
                                 positions, ctx, f"tail/sub{i}",
                                 cache=c_i, idx=idx, state_len=state_len,
                                 implicit_positions=implicit_positions)
            stats_out.update(ctx.stats)
            if nc is not None:
                ncache_tail[f"sub{i}"] = nc
        if cache is not None:
            new_cache = {"units": ncache_units, "tail": ncache_tail}
    else:
        for prefix, kind, _ in stacks(cfg):
            layer_cache = cache.get(prefix) if cache else None
            x, ncache = run_seq(prefix, kind, params[prefix], x,
                                layer_cache)
            if cache is not None:
                new_cache = dict(new_cache or {}, **{prefix: ncache})
    return x, stats_out, new_cache


def forward(cfg, params, batch, taps=None, collect=False, cache=None,
            train=False, last_only=False, last_pos=None):
    """Returns (logits, stats, new_cache). ``last_only`` computes the
    vocab projection for the final position only (prefill: the other
    T-1 logits are dead code and the vocab matmul dominates prefill
    FLOPs for small models — EXPERIMENTS.md §Perf). ``last_pos`` (B,)
    generalizes it to a per-row gather position (bucketed prefill, where
    the last real token sits before the padded tail).

    ``cache["idx"]`` is a scalar for static decode, or a (B,) per-slot
    length vector for the serving pool (repro.serve). ``cache["table"]``
    (B, nbps), if present, switches attention to the block-paged layout
    (repro.serve.paged); the table itself is carried through unchanged."""
    idx = cache["idx"] if cache is not None else None
    table = cache.get("table") if cache is not None else None
    if "positions" in batch:
        positions = batch["positions"]
    else:
        B, T = batch["tokens"].shape
        base = jnp.arange(T, dtype=jnp.int32)[None, :]
        if idx is not None:
            base = base + (idx[:, None] if idx.ndim == 1 else idx)
        positions = jnp.broadcast_to(base, (B, T))

    # a padded prefill (per-row last_pos on a multi-token batch) tells
    # recurrent mixers where each row's real prefix ends
    state_len = None
    if (cache is not None and last_pos is not None
            and batch["tokens"].shape[1] > 1):
        state_len = jnp.asarray(last_pos) + 1

    x = _embed(cfg, params, batch, positions)
    x, stats, new_cache = _scan_layers(
        cfg, params, x, positions, taps, collect, cache, idx, train,
        table=table, state_len=state_len,
        implicit_positions="positions" not in batch and cache is None)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if last_only:
        x = x[:, -1:]
    elif last_pos is not None:
        x = jnp.take_along_axis(
            x, last_pos[:, None, None].astype(jnp.int32), axis=1)
    logits = _logits(cfg, params, x)
    if new_cache is not None:
        new_cache["idx"] = idx + batch["tokens"].shape[1]
        if table is not None:
            new_cache["table"] = table
    return logits, stats, new_cache


@jax.named_scope("head")
def loss_from_logits(cfg, logits, batch):
    """Next-token cross-entropy from full-sequence logits — the tail of
    :func:`loss_fn`, shared with the pipeline's last stage
    (``repro.pipeline``) so both paths compute the identical loss."""
    del cfg
    labels = batch["tokens"][:, 1:]
    lg = logits[:, :-1].astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    mask = batch.get("mask")
    nll = logz - gold
    if mask is not None:
        m = mask[:, 1:].astype(jnp.float32)
        return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
    return jnp.mean(nll)


def loss_fn(cfg, params, batch, taps=None, collect=False):
    """Next-token cross-entropy. Returns (loss, stats)."""
    logits, stats, _ = forward(cfg, params, batch, taps=taps,
                               collect=collect, train=True)
    return loss_from_logits(cfg, logits, batch), stats


# ---------------------------------------------------------------------------
# Per-stage slices (pipeline parallelism, repro.pipeline)
# ---------------------------------------------------------------------------

def check_pipeline(cfg):
    """Refuse what the pipeline stage program cannot run."""
    if cfg.dropless or cfg.n_dense_layers:
        raise NotImplementedError(
            f"{cfg.name}: the pipeline stage program has no dropless expert "
            f"exchange (the all-to-all of the expert-share layer) and no "
            f"stage for the leading dense stack")


def embed_inputs(cfg, params, batch, positions):
    """Stage-0 front of the pipelined forward: token embedding (+ VLM
    image projection). Public alias of the internal embed so the
    pipeline executor and :func:`forward` trace the same ops."""
    return _embed(cfg, params, batch, positions)


def stage_slice_forward(cfg, layer_stack, x, positions, *, train=True,
                        valid=None):
    """Run a contiguous slice of the scanned decoder stack — the
    per-stage body of the pipeline executor.

    ``layer_stack`` is the ``params["layers"]`` subtree restricted to
    this stage's ``(K, ...)`` layers (the ``stage``-sharded slice) —
    or, for the hybrid family, the ``params["units"]`` subtree sliced
    to ``(K, ...)`` pattern units. ``valid`` is an optional ``(K,)``
    bool mask for non-uniform partitions: stages padded to the max
    slice length skip their padding entries via ``jnp.where`` (padding
    duplicates a real layer, so both branches stay finite and the
    discarded branch contributes exactly-zero parameter gradients).
    Train-mode only: no KV caches, no stats taps (the SU graph runs as
    its own amortized program), per-layer remat as in :func:`forward`.
    """
    if cfg.family == "audio":
        raise NotImplementedError(
            "audio stacks pipeline through whisper.stage_slice_forward")
    check_pipeline(cfg)
    if cfg.family == "hybrid":
        def body(xcur, xs):
            p_u, ok = xs
            ctx = Ctx(taps=None, collect=False, soi_block=cfg.soi_block)
            xnew = xcur
            for i, kind in enumerate(cfg.pattern):
                xnew, _ = _layer_apply(cfg, kind, p_u[f"sub{i}"], xnew,
                                       positions, ctx, f"units/sub{i}",
                                       cache=None, idx=None)
            if ok is not None:
                xnew = jnp.where(ok, xnew, xcur)
            return xnew, None
    else:
        kind = layer_plan(cfg)[0]

        def body(xcur, xs):
            p_l, ok = xs
            ctx = Ctx(taps=None, collect=False, soi_block=cfg.soi_block)
            xnew, _ = _layer_apply(cfg, kind, p_l, xcur, positions, ctx,
                                   "layers", cache=None, idx=None)
            if ok is not None:
                xnew = jnp.where(ok, xnew, xcur)
            return xnew, None

    fn = jax.checkpoint(body) if (train and cfg.remat) else body
    x, _ = jax.lax.scan(fn, x, (layer_stack, valid))
    return x


def tail_forward(cfg, params, x, positions):
    """Hybrid-family pipelined tail: the ``n_layers % len(pattern)``
    trailing sub-layers that don't fill a pattern unit. Runs on the
    last stage (tail params are stage-replicated; the stage psum on
    their gradients collects the last stage's contribution)."""
    _, tail = _hybrid_split(cfg)
    ctx = Ctx(taps=None, collect=False, soi_block=cfg.soi_block)
    for i, kind in enumerate(tail):
        x, _ = _layer_apply(cfg, kind, params["tail"][f"sub{i}"], x,
                            positions, ctx, f"tail/sub{i}",
                            cache=None, idx=None)
    return x


def head_loss(cfg, params, x, batch):
    """Last-stage tail of the pipelined forward: final norm + vocab
    head + :func:`loss_from_logits` — the identical math the monolithic
    :func:`loss_fn` runs after its layer scan."""
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return loss_from_logits(cfg, _logits(cfg, params, x), batch)


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

_NO_LATENT_CACHE = (
    "latent attention (MLA) has no serving cache here: it needs a latent "
    "cache (kv_lora_rank + qk_rope_dim per token), which the KV pools "
    "(serve/pool, serve/paged) do not have")


def check_serving(cfg):
    """Refuse what the serving caches cannot hold."""
    if cfg.mla:
        raise NotImplementedError(f"{cfg.name}: {_NO_LATENT_CACHE}")


def init_cache(cfg, batch: int, seq_len: int, dtype=jnp.bfloat16) -> Dict:
    check_serving(cfg)
    kv, hd = cfg.n_kv_heads, cfg.hd

    def attn_cache(S):
        # unwritten slots carry a far-future position so the causal mask
        # excludes them
        return {
            "k": jnp.zeros((batch, S, kv, hd), dtype),
            "v": jnp.zeros((batch, S, kv, hd), dtype),
            "pos": jnp.full((batch, S), UNWRITTEN_POS, jnp.int32),
        }

    if cfg.family == "hybrid":
        n_units, tail = _hybrid_split(cfg)
        S = min(seq_len, cfg.window or seq_len)

        def unit_cache(_):
            return {f"sub{i}":
                    attn_cache(S) if kind in ("attn", "local")
                    else rglru_mod.init_rglru_state(cfg, batch)
                    for i, kind in enumerate(cfg.pattern)}

        units = jax.vmap(unit_cache)(jnp.arange(n_units))
        tail_c = {f"sub{i}":
                  attn_cache(S) if kind in ("attn", "local")
                  else rglru_mod.init_rglru_state(cfg, batch)
                  for i, kind in enumerate(tail)}
        return {"units": units, "tail": tail_c,
                "idx": jnp.zeros((), jnp.int32)}
    if cfg.family == "ssm":
        def one(_):
            return ssm_mod.init_mamba_state(cfg, batch)
        layers = jax.vmap(one)(jnp.arange(cfg.n_layers))
        return {"layers": layers, "idx": jnp.zeros((), jnp.int32)}

    def one(_):
        return attn_cache(seq_len)
    layers = jax.vmap(one)(jnp.arange(cfg.n_layers))
    return {"layers": layers, "idx": jnp.zeros((), jnp.int32)}


def prefill(cfg, params, batch, cache, length=None):
    """Process a prompt; returns (last-token logits, cache).

    ``length`` (B,) gives the real prompt length per row when the
    prompt is right-padded to a bucket size (serving engine): logits are
    gathered at the last *real* token instead of the padded tail."""
    logits, _, cache = forward(
        cfg, params, batch, cache=cache, last_only=length is None,
        last_pos=None if length is None else jnp.asarray(length) - 1)
    return logits[:, -1], cache


def decode_step(cfg, params, token, cache):
    """One decode step. ``token``: (B, 1) int32."""
    logits, _, cache = forward(cfg, params, {"tokens": token}, cache=cache)
    return logits[:, -1], cache


def cache_write_slot(cache, slot, row_cache, length):
    """Insert a single-request prefill cache into slot ``slot`` of a
    serving pool (a cache whose batch dim is slots and whose ``idx`` is
    a per-slot length vector — see repro.serve.pool)."""
    from repro.serve.pool import write_slot
    return write_slot(cache, slot, row_cache, length)


def cache_reset_slot(cache, slot):
    """Free slot ``slot``: length 0, positions -> far-future sentinel,
    recurrent state -> 0 (see repro.serve.pool)."""
    from repro.serve.pool import reset_slot
    return reset_slot(cache, slot)


# ---------------------------------------------------------------------------
# K-FAC registry
# ---------------------------------------------------------------------------

def kfac_specs(cfg) -> Dict[str, LinearSpec]:
    """All factored linears with path-accurate names (DESIGN.md §4)."""
    d, f = cfg.d_model, cfg.d_ff
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    specs: Dict[str, LinearSpec] = {}

    def attn_mlp(prefix, stack, with_mlp=True):
        specs[f"{prefix}/attn/wq"] = LinearSpec(d, h * hd, stack)
        specs[f"{prefix}/attn/wk"] = LinearSpec(
            d, kv * hd, stack, share_a_with=f"{prefix}/attn/wq")
        specs[f"{prefix}/attn/wv"] = LinearSpec(
            d, kv * hd, stack, share_a_with=f"{prefix}/attn/wq")
        specs[f"{prefix}/attn/wo"] = LinearSpec(h * hd, d, stack)
        if with_mlp:
            mlp(prefix, stack)

    def mlp(prefix, stack, width=f, sub="mlp"):
        specs[f"{prefix}/{sub}/wg"] = LinearSpec(d, width, stack)
        specs[f"{prefix}/{sub}/wu"] = LinearSpec(
            d, width, stack, share_a_with=f"{prefix}/{sub}/wg")
        specs[f"{prefix}/{sub}/wd"] = LinearSpec(width, d, stack)

    def mla(prefix, stack):
        # kv_a shares q's input Gram; kv_b's A is the normed latent's
        r, rope = cfg.kv_lora_rank, cfg.qk_rope_dim
        dv = cfg.v_head_dim
        specs[f"{prefix}/mla/wq"] = LinearSpec(d, h * cfg.qk_dim, stack)
        specs[f"{prefix}/mla/wkv_a"] = LinearSpec(
            d, r + rope, stack, share_a_with=f"{prefix}/mla/wq")
        specs[f"{prefix}/mla/wkv_b"] = LinearSpec(
            r, h * (cfg.qk_nope_dim + dv), stack)
        specs[f"{prefix}/mla/wo"] = LinearSpec(h * dv, d, stack)

    if cfg.family in ("dense", "vlm"):
        attn_mlp("layers", (cfg.n_layers,))
    elif cfg.dropless:
        if cfg.n_dense_layers:
            stack = (cfg.n_dense_layers,)
            mla("dense_layers", stack)
            mlp("dense_layers", stack)
        L, fe = cfg.n_moe_layers, cfg.d_expert_
        mla("layers", (L,))
        mlp("layers", (L,), cfg.n_shared_experts * fe, "shared")
        stack = (L, cfg.held)
        specs["layers/moe/wg"] = LinearSpec(d, fe, stack, grouped=True)
        specs["layers/moe/wu"] = LinearSpec(
            d, fe, stack, share_a_with="layers/moe/wg", grouped=True)
        specs["layers/moe/wd"] = LinearSpec(fe, d, stack, grouped=True)
    elif cfg.family == "moe":
        L = cfg.n_layers
        attn_mlp("layers", (L,), with_mlp=False)
        e = cfg.n_experts
        specs["layers/moe/wg"] = LinearSpec(d, f, (L, e), cap_tokens=True)
        specs["layers/moe/wu"] = LinearSpec(
            d, f, (L, e), share_a_with="layers/moe/wg", cap_tokens=True)
        specs["layers/moe/wd"] = LinearSpec(f, d, (L, e), cap_tokens=True)
    elif cfg.family == "ssm":
        L = cfg.n_layers
        di, n, dr = cfg.d_inner, cfg.ssm_state, cfg.dt_rank_
        specs["layers/mamba/in_proj"] = LinearSpec(d, 2 * di, (L,))
        specs["layers/mamba/x_proj"] = LinearSpec(di, dr + 2 * n, (L,))
        specs["layers/mamba/dt_proj"] = LinearSpec(dr, di, (L,))
        specs["layers/mamba/out_proj"] = LinearSpec(di, d, (L,))
    elif cfg.family == "hybrid":
        n_units, tail = _hybrid_split(cfg)

        def rec_specs(prefix, stack):
            lw = cfg.lru_width_
            specs[f"{prefix}/rec/in_x"] = LinearSpec(d, lw, stack)
            specs[f"{prefix}/rec/in_gate"] = LinearSpec(
                d, lw, stack, share_a_with=f"{prefix}/rec/in_x")
            specs[f"{prefix}/rec/w_a"] = LinearSpec(lw, lw, stack)
            specs[f"{prefix}/rec/w_x"] = LinearSpec(
                lw, lw, stack, share_a_with=f"{prefix}/rec/w_a")
            specs[f"{prefix}/rec/out"] = LinearSpec(lw, d, stack)
            mlp(prefix, stack)

        for i, kind in enumerate(cfg.pattern):
            pfx = f"units/sub{i}"
            if kind in ("attn", "local"):
                attn_mlp(pfx, (n_units,))
            else:
                rec_specs(pfx, (n_units,))
        for i, kind in enumerate(tail):
            pfx = f"tail/sub{i}"
            if kind in ("attn", "local"):
                attn_mlp(pfx, ())
            else:
                rec_specs(pfx, ())
    return specs


def build_taps(cfg, specs: Dict[str, LinearSpec], n_tokens: int) -> Dict:
    """Zero taps sized for a stats pass over ``n_tokens`` tokens; a
    grouped (per-expert) linear's tap is the size of its G factor."""
    from repro.core import soi

    out = {}
    for name, s in specs.items():
        if s.grouped:
            out[name] = jnp.zeros(
                soi.factor_shapes(s, cfg.soi_block)["G"], jnp.float32)
            continue
        t = moe_mod.capacity(cfg, n_tokens) if s.cap_tokens else n_tokens
        out[name] = jnp.zeros(s.stack + (t, s.d_out), jnp.float32)
    return out
