"""Whisper-style encoder-decoder backbone (whisper-tiny arch).

The conv/mel frontend is a STUB per the assignment: ``input_specs``
provides precomputed frame embeddings (B, T_frames, D). The backbone is
faithful: pre-LN transformer, bidirectional encoder, causal decoder with
cross-attention, GELU MLPs, LayerNorm with bias, sinusoidal positions,
tied embedding/output head.

Shape-cell semantics (DESIGN.md §4): ``train`` = teacher-forced CE over
T decoder tokens with T encoder frames; ``prefill`` = encode T frames +
short decoder prompt; ``decode`` = one decoder token against cached
encoder output of T frames and a T-slot self-attention cache.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.soi import LinearSpec
from repro.dist.api import BATCH_AXES, MODEL, shard_hint
from repro.models.layers import (
    Ctx,
    attention,
    cast,
    dense,
    gelu,
    kv_cache_update,
    layer_norm,
    pos_cache_update,
    shard_acts,
)


def _sinusoid(positions: jax.Array, d: int) -> jax.Array:
    """(B, T) -> (B, T, d) sinusoidal embedding."""
    half = d // 2
    freqs = jnp.exp(-jnp.log(10000.0) * jnp.arange(half) / (half - 1))
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _init_ln(d, key=None):
    return {"w": jnp.ones((d,), jnp.float32),
            "b": jnp.zeros((d,), jnp.float32)}


def _init_attn(cfg, key):
    d, h, hd = cfg.d_model, cfg.n_heads, cfg.hd
    ks = jax.random.split(key, 4)
    s = d ** -0.5
    return {
        "wq": jax.random.normal(ks[0], (d, h * hd), jnp.float32) * s,
        "wk": jax.random.normal(ks[1], (d, h * hd), jnp.float32) * s,
        "wv": jax.random.normal(ks[2], (d, h * hd), jnp.float32) * s,
        "wo": jax.random.normal(ks[3], (h * hd, d), jnp.float32)
        * (h * hd) ** -0.5,
        "bq": jnp.zeros((h * hd,), jnp.float32),
        "bv": jnp.zeros((h * hd,), jnp.float32),
        "bo": jnp.zeros((d,), jnp.float32),
    }


def _init_mlp(cfg, key):
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 2)
    return {
        "w1": jax.random.normal(ks[0], (d, f), jnp.float32) * d ** -0.5,
        "b1": jnp.zeros((f,), jnp.float32),
        "w2": jax.random.normal(ks[1], (f, d), jnp.float32) * f ** -0.5,
        "b2": jnp.zeros((d,), jnp.float32),
    }


def _init_enc_layer(cfg, key):
    ks = jax.random.split(key, 2)
    return {"ln1": _init_ln(cfg.d_model), "attn": _init_attn(cfg, ks[0]),
            "ln2": _init_ln(cfg.d_model), "mlp": _init_mlp(cfg, ks[1])}


def _init_dec_layer(cfg, key):
    ks = jax.random.split(key, 3)
    return {"ln1": _init_ln(cfg.d_model), "attn": _init_attn(cfg, ks[0]),
            "lnx": _init_ln(cfg.d_model), "cross": _init_attn(cfg, ks[1]),
            "ln2": _init_ln(cfg.d_model), "mlp": _init_mlp(cfg, ks[2])}


def init(cfg, key) -> Dict:
    ks = jax.random.split(key, 4)
    enc_keys = jax.random.split(ks[0], cfg.n_enc_layers)
    dec_keys = jax.random.split(ks[1], cfg.n_dec_layers)
    return {
        "embed": jax.random.normal(ks[2], (cfg.vocab, cfg.d_model),
                                   jnp.float32) * 0.02,
        "enc": jax.vmap(lambda k: _init_enc_layer(cfg, k))(enc_keys),
        "dec": jax.vmap(lambda k: _init_dec_layer(cfg, k))(dec_keys),
        "enc_ln_f": _init_ln(cfg.d_model),
        "dec_ln_f": _init_ln(cfg.d_model),
    }


@jax.named_scope("attn")
def _mha(cfg, p, xq, xkv, ctx, prefix, causal, q_pos, kv_pos,
         cache=None, idx=None, shared_kv=None):
    """One attention with optional cache / precomputed kv."""
    B, T, D = xq.shape
    h, hd = cfg.n_heads, cfg.hd
    if xkv is None:
        xkv = xq
    q = dense(xq, p["wq"], f"{prefix}/wq", ctx, bias=p["bq"])
    if shared_kv is not None:
        k, v = shared_kv
    else:
        k = dense(xkv, p["wk"], f"{prefix}/wk", ctx, collect_gram=False)
        v = dense(xkv, p["wv"], f"{prefix}/wv", ctx, bias=p["bv"],
                  collect_gram=False)
        k = k.reshape(B, -1, h, hd)
        v = v.reshape(B, -1, h, hd)
    q = q.reshape(B, T, h, hd)
    new_cache = None
    if cache is not None:
        ck, cv = kv_cache_update(cache["k"], cache["v"], k, v, idx)
        cpos = pos_cache_update(cache["pos"], q_pos, idx)
        k, v, kv_pos = ck.astype(q.dtype), cv.astype(q.dtype), cpos
        new_cache = {"k": ck, "v": cv, "pos": cpos}
    out = attention(q, k, v, q_pos, kv_pos, causal=causal,
                    chunk=cfg.attn_chunk if T > cfg.attn_chunk else 0)
    out = out.reshape(B, T, h * hd)
    out = dense(out, p["wo"], f"{prefix}/wo", ctx, bias=p["bo"])
    return out, new_cache


@jax.named_scope("mlp")
def _mlp(cfg, p, x, ctx, prefix):
    hidden = gelu(dense(x, p["w1"], f"{prefix}/w1", ctx, bias=p["b1"]))
    hidden = shard_hint(hidden, BATCH_AXES, None, MODEL)
    return dense(hidden, p["w2"], f"{prefix}/w2", ctx, bias=p["b2"])


def encode(cfg, params, enc_embeds, ctx_opts=None, taps=None,
           collect=False):
    """enc_embeds: (B, T, D) stubbed frame embeddings -> (B, T, D)."""
    B, T, D = enc_embeds.shape
    dt = jnp.dtype(cfg.dtype)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    x = (enc_embeds.astype(jnp.float32) + _sinusoid(pos, D)).astype(dt)
    x = shard_acts(x)
    stats_all = {}

    def body(xc, xs):
        p_l, taps_l = xs
        ctx = Ctx(taps=taps_l or None, collect=collect,
                  soi_block=cfg.soi_block)
        h, _ = _mha(cfg, p_l["attn"],
                    layer_norm(xc, p_l["ln1"]["w"], p_l["ln1"]["b"]),
                    None, ctx, "enc/attn", False, pos, pos)
        xc = xc + h
        xc = xc + _mlp(cfg, p_l["mlp"],
                       layer_norm(xc, p_l["ln2"]["w"], p_l["ln2"]["b"]),
                       ctx, "enc/mlp")
        return xc, ctx.stats

    taps_xs = {k: v for k, v in (taps or {}).items()
               if k.startswith("enc/")}
    fn = jax.checkpoint(body) if cfg.remat else body
    x, stats = jax.lax.scan(fn, x, (params["enc"], taps_xs))
    stats_all.update(stats)
    x = layer_norm(x, params["enc_ln_f"]["w"], params["enc_ln_f"]["b"])
    return x, stats_all


def _mha_kv(cfg, p, xkv, ctx, prefix):
    B = xkv.shape[0]
    h, hd = cfg.n_heads, cfg.hd
    k = dense(xkv, p["wk"], f"{prefix}/wk", ctx)
    v = dense(xkv, p["wv"], f"{prefix}/wv", ctx, bias=p["bv"],
              collect_gram=False)
    return k.reshape(B, -1, h, hd), v.reshape(B, -1, h, hd)


@jax.named_scope("head")
def _head_logits(cfg, params, x):
    """Tied vocab head on post-``dec_ln_f`` activations.

    The vocab is padded to a shardable multiple of 128 (whisper's
    51865 is not 16-divisible => unsharded logits dominate HBM
    otherwise); padded columns are masked so loss/argmax are
    unchanged."""
    dt = x.dtype
    head = params["embed"].T
    v = head.shape[-1]
    vpad = (-v) % 128
    if vpad:
        head = jnp.pad(head, ((0, 0), (0, vpad)))
    logits = jax.lax.dot_general(
        x, cast(head, dt), (((2,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    if vpad:
        logits = logits + jnp.where(jnp.arange(v + vpad) < v, 0.0,
                                    -1e30)
    return shard_hint(logits, BATCH_AXES, None, MODEL)


@jax.named_scope("head")
def loss_from_logits(cfg, logits, batch):
    """Teacher-forced CE over decoder tokens — the tail shared by the
    monolithic :func:`loss_fn` and the pipeline's last stage."""
    del cfg
    labels = batch["tokens"][:, 1:]
    lg = logits[:, :-1].astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def decode(cfg, params, tokens, enc_out, taps=None, collect=False,
           cache=None, last_only=False, last_pos=None):
    """Decoder pass. tokens: (B, T). Returns (logits, stats, new_cache).
    ``last_only`` projects only the final position onto the vocab
    (prefill path — see models/lm.forward); ``last_pos`` (B,) is the
    per-row variant (bucketed prefill). ``cache["idx"]`` may be a (B,)
    per-slot length vector on the serving-pool path."""
    B, T = tokens.shape
    D = cfg.d_model
    dt = jnp.dtype(cfg.dtype)
    idx = cache["idx"] if cache is not None else None
    base = jnp.arange(T, dtype=jnp.int32)[None, :]
    off = 0 if idx is None else (idx[:, None] if idx.ndim == 1 else idx)
    pos = jnp.broadcast_to(base + off, (B, T))
    x = (cast(params["embed"], dt)[tokens].astype(jnp.float32)
         + _sinusoid(pos, D)).astype(dt)
    x = shard_acts(x)
    enc_pos = jnp.broadcast_to(
        jnp.arange(enc_out.shape[1], dtype=jnp.int32),
        (B, enc_out.shape[1]))

    def body(xc, xs):
        p_l, taps_l, cache_l = xs
        ctx = Ctx(taps=taps_l or None, collect=collect,
                  soi_block=cfg.soi_block)
        self_cache = cache_l["self"] if cache_l is not None else None
        h, nself = _mha(cfg, p_l["attn"],
                        layer_norm(xc, p_l["ln1"]["w"], p_l["ln1"]["b"]),
                        None, ctx, "dec/attn", True, pos, pos,
                        cache=self_cache, idx=idx)
        xc = xc + h
        xq = layer_norm(xc, p_l["lnx"]["w"], p_l["lnx"]["b"])
        if cache_l is not None:
            kv = (cache_l["cross_k"].astype(xq.dtype),
                  cache_l["cross_v"].astype(xq.dtype))
        else:
            kv = _mha_kv(cfg, p_l["cross"], enc_out, ctx, "dec/cross")
        h, _ = _mha(cfg, p_l["cross"], xq, None, ctx, "dec/cross", False,
                    pos, enc_pos, shared_kv=kv)
        xc = xc + h
        xc = xc + _mlp(cfg, p_l["mlp"],
                       layer_norm(xc, p_l["ln2"]["w"], p_l["ln2"]["b"]),
                       ctx, "dec/mlp")
        ncache = {"self": nself} if cache_l is not None else None
        return xc, (ctx.stats, ncache)

    taps_xs = {k: v for k, v in (taps or {}).items()
               if k.startswith("dec/")}
    layer_cache = cache["layers"] if cache is not None else None
    # remat on the training path only (decode carries a cache)
    fn = jax.checkpoint(body) if (cfg.remat and cache is None) else body
    x, (stats, ncache) = jax.lax.scan(
        fn, x, (params["dec"], taps_xs, layer_cache))
    x = layer_norm(x, params["dec_ln_f"]["w"], params["dec_ln_f"]["b"])
    if last_only:
        x = x[:, -1:]
    elif last_pos is not None:
        x = jnp.take_along_axis(
            x, last_pos[:, None, None].astype(jnp.int32), axis=1)
    logits = _head_logits(cfg, params, x)
    new_cache = None
    if cache is not None:
        new_cache = {
            "layers": {"self": ncache["self"],
                       "cross_k": cache["layers"]["cross_k"],
                       "cross_v": cache["layers"]["cross_v"]},
            "idx": idx + T,
        }
    return logits, stats, new_cache


def loss_fn(cfg, params, batch, taps=None, collect=False):
    enc_out, stats_e = encode(cfg, params, batch["enc_embeds"],
                              taps=taps, collect=collect)
    logits, stats_d, _ = decode(cfg, params, batch["tokens"], enc_out,
                                taps=taps, collect=collect)
    loss = loss_from_logits(cfg, logits, batch)
    stats = {**stats_e, **stats_d}
    return loss, stats


# ---------------------------------------------------------------------------
# Per-stage slices (pipeline parallelism, repro.pipeline)
# ---------------------------------------------------------------------------
#
# The pipeline channel for the enc-dec stack is the CONCATENATION
# [enc_seg | dec_seg] along time, width T_enc + T_dec: encoder layers
# live on leading stages and decoder layers on trailing ones (the
# contiguous stage partition over [enc..., dec...] atoms pins them
# there), and the concatenated channel carries both the final encoder
# output forward to every decoder stage *and* the encoder cotangents
# backward — no extra cross-stage traffic beyond the one channel
# ppermute per tick. A stage that runs decoder layers recomputes
# ``enc_out = layer_norm(enc_seg, enc_ln_f)`` locally (enc_ln_f is
# stage-replicated); by partition contiguity the enc segment is final
# on every such stage.


def stage_channel_init(cfg, params, batch):
    """Stage-0 front of the pipelined forward: both frontends — frame
    embeddings + sinusoid for the encoder segment, token embedding +
    sinusoid for the decoder segment — concatenated along time."""
    tokens = batch["tokens"]
    B, T_dec = tokens.shape
    D = cfg.d_model
    dt = jnp.dtype(cfg.dtype)
    enc = batch["enc_embeds"]
    T_enc = enc.shape[1]
    epos = jnp.broadcast_to(jnp.arange(T_enc, dtype=jnp.int32),
                            (B, T_enc))
    enc_x = (enc.astype(jnp.float32) + _sinusoid(epos, D)).astype(dt)
    dpos = jnp.broadcast_to(jnp.arange(T_dec, dtype=jnp.int32),
                            (B, T_dec))
    dec_x = (cast(params["embed"], dt)[tokens].astype(jnp.float32)
             + _sinusoid(dpos, D)).astype(dt)
    return jnp.concatenate([enc_x, dec_x], axis=1)


def stage_slice_forward(cfg, params, ch, t_enc, *, enc_valid=None,
                        dec_valid=None, train=True):
    """Per-stage body of the pipelined enc-dec forward.

    ``params["enc"]``/``params["dec"]`` arrive as this stage's padded
    ``(Ke, ...)``/``(Kd, ...)`` slices; ``enc_valid``/``dec_valid``
    (bool ``(Ke,)``/``(Kd,)``) mask the padding entries (duplicates of
    real layers, so the discarded branch stays finite and its parameter
    gradients are exactly zero). Train-mode only."""
    B = ch.shape[0]
    enc_seg, dec_seg = ch[:, :t_enc], ch[:, t_enc:]
    T_dec = dec_seg.shape[1]
    epos = jnp.broadcast_to(jnp.arange(t_enc, dtype=jnp.int32),
                            (B, t_enc))
    dpos = jnp.broadcast_to(jnp.arange(T_dec, dtype=jnp.int32),
                            (B, T_dec))

    def ebody(xc, xs):
        p_l, ok = xs
        ctx = Ctx(taps=None, collect=False, soi_block=cfg.soi_block)
        h, _ = _mha(cfg, p_l["attn"],
                    layer_norm(xc, p_l["ln1"]["w"], p_l["ln1"]["b"]),
                    None, ctx, "enc/attn", False, epos, epos)
        xn = xc + h
        xn = xn + _mlp(cfg, p_l["mlp"],
                       layer_norm(xn, p_l["ln2"]["w"], p_l["ln2"]["b"]),
                       ctx, "enc/mlp")
        if ok is not None:
            xn = jnp.where(ok, xn, xc)
        return xn, None

    efn = jax.checkpoint(ebody) if (train and cfg.remat) else ebody
    enc_seg, _ = jax.lax.scan(efn, enc_seg, (params["enc"], enc_valid))

    # final by contiguity on every stage whose dec slice has a valid
    # entry; on pure-encoder stages the dec scan is fully masked and
    # this value (and its zero cotangent) is dead
    enc_out = layer_norm(enc_seg, params["enc_ln_f"]["w"],
                         params["enc_ln_f"]["b"])

    def dbody(xc, xs):
        p_l, ok = xs
        ctx = Ctx(taps=None, collect=False, soi_block=cfg.soi_block)
        h, _ = _mha(cfg, p_l["attn"],
                    layer_norm(xc, p_l["ln1"]["w"], p_l["ln1"]["b"]),
                    None, ctx, "dec/attn", True, dpos, dpos)
        xn = xc + h
        xq = layer_norm(xn, p_l["lnx"]["w"], p_l["lnx"]["b"])
        kv = _mha_kv(cfg, p_l["cross"], enc_out, ctx, "dec/cross")
        h, _ = _mha(cfg, p_l["cross"], xq, None, ctx, "dec/cross",
                    False, dpos, epos, shared_kv=kv)
        xn = xn + h
        xn = xn + _mlp(cfg, p_l["mlp"],
                       layer_norm(xn, p_l["ln2"]["w"], p_l["ln2"]["b"]),
                       ctx, "dec/mlp")
        if ok is not None:
            xn = jnp.where(ok, xn, xc)
        return xn, None

    dfn = jax.checkpoint(dbody) if (train and cfg.remat) else dbody
    dec_seg, _ = jax.lax.scan(dfn, dec_seg, (params["dec"], dec_valid))
    return jnp.concatenate([enc_seg, dec_seg], axis=1)


def head_loss(cfg, params, ch, batch):
    """Last-stage tail of the pipelined forward: dec final norm + tied
    vocab head + :func:`loss_from_logits` on the decoder segment of the
    channel — the identical math :func:`loss_fn` runs after decode."""
    t_enc = batch["enc_embeds"].shape[1]
    x = ch[:, t_enc:]
    x = layer_norm(x, params["dec_ln_f"]["w"], params["dec_ln_f"]["b"])
    return loss_from_logits(cfg, _head_logits(cfg, params, x), batch)


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def init_cache(cfg, batch: int, self_len: int, enc_len: int,
               dtype=jnp.bfloat16) -> Dict:
    h, hd = cfg.n_heads, cfg.hd
    L = cfg.n_dec_layers

    def one(_):
        return {
            "self": {
                "k": jnp.zeros((batch, self_len, h, hd), dtype),
                "v": jnp.zeros((batch, self_len, h, hd), dtype),
                "pos": jnp.full((batch, self_len), 2 ** 30, jnp.int32),
            },
            "cross_k": jnp.zeros((batch, enc_len, h, hd), dtype),
            "cross_v": jnp.zeros((batch, enc_len, h, hd), dtype),
        }

    return {"layers": jax.vmap(one)(jnp.arange(L)),
            "idx": jnp.zeros((), jnp.int32)}


def prefill(cfg, params, batch, cache, length=None):
    """Encode frames + prefill the decoder prompt. ``length`` (B,) gives
    per-row real prompt lengths for bucket-padded prompts (serving)."""
    enc_out, _ = encode(cfg, params, batch["enc_embeds"])

    # precompute cross k/v per decoder layer into the cache
    def kv_body(_, p_l):
        k, v = _mha_kv(cfg, p_l["cross"], enc_out, None, "dec/cross")
        return None, (k, v)

    _, (cks, cvs) = jax.lax.scan(kv_body, None, params["dec"])
    cache = dict(cache)
    layers = dict(cache["layers"])
    layers["cross_k"] = cks.astype(cache["layers"]["cross_k"].dtype)
    layers["cross_v"] = cvs.astype(cache["layers"]["cross_v"].dtype)
    cache["layers"] = layers

    logits, _, cache = decode(
        cfg, params, batch["tokens"], enc_out, cache=cache,
        last_only=length is None,
        last_pos=None if length is None else jnp.asarray(length) - 1)
    return logits[:, -1], cache


def decode_step(cfg, params, token, cache):
    B = token.shape[0]
    enc_len = cache["layers"]["cross_k"].shape[2]
    dummy_enc = jnp.zeros((B, enc_len, cfg.d_model),
                          jnp.dtype(cfg.dtype))
    logits, _, cache = decode(cfg, params, token, dummy_enc, cache=cache)
    return logits[:, -1], cache


def cache_write_slot(cache, slot, row_cache, length):
    """Insert a single-request prefill cache (self + cross KV) into slot
    ``slot`` of a serving pool (see repro.serve.pool)."""
    from repro.serve.pool import write_slot
    return write_slot(cache, slot, row_cache, length)


def cache_reset_slot(cache, slot):
    """Free slot ``slot`` of a serving pool (see repro.serve.pool)."""
    from repro.serve.pool import reset_slot
    return reset_slot(cache, slot)


def kfac_specs(cfg) -> Dict[str, LinearSpec]:
    d, f, h, hd = cfg.d_model, cfg.d_ff, cfg.n_heads, cfg.hd
    Le, Ld = (cfg.n_enc_layers,), (cfg.n_dec_layers,)
    specs = {}
    for pfx, st in (("enc", Le),):
        specs[f"{pfx}/attn/wq"] = LinearSpec(d, h * hd, st)
        specs[f"{pfx}/attn/wk"] = LinearSpec(d, h * hd, st,
                                             share_a_with=f"{pfx}/attn/wq")
        specs[f"{pfx}/attn/wv"] = LinearSpec(d, h * hd, st,
                                             share_a_with=f"{pfx}/attn/wq")
        specs[f"{pfx}/attn/wo"] = LinearSpec(h * hd, d, st)
        specs[f"{pfx}/mlp/w1"] = LinearSpec(d, f, st)
        specs[f"{pfx}/mlp/w2"] = LinearSpec(f, d, st)
    specs["dec/attn/wq"] = LinearSpec(d, h * hd, Ld)
    specs["dec/attn/wk"] = LinearSpec(d, h * hd, Ld,
                                      share_a_with="dec/attn/wq")
    specs["dec/attn/wv"] = LinearSpec(d, h * hd, Ld,
                                      share_a_with="dec/attn/wq")
    specs["dec/attn/wo"] = LinearSpec(h * hd, d, Ld)
    specs["dec/cross/wq"] = LinearSpec(d, h * hd, Ld)
    specs["dec/cross/wk"] = LinearSpec(d, h * hd, Ld)
    specs["dec/cross/wv"] = LinearSpec(d, h * hd, Ld,
                                       share_a_with="dec/cross/wk")
    specs["dec/cross/wo"] = LinearSpec(h * hd, d, Ld)
    specs["dec/mlp/w1"] = LinearSpec(d, f, Ld)
    specs["dec/mlp/w2"] = LinearSpec(f, d, Ld)
    return specs
