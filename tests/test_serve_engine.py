"""Continuous-batching serving engine: scheduler behavior, slot-pool
insert/reset, on-device sampling, jitted decode-loop parity with the
static path, termination (budget + EOS) and slot reuse across a
mixed-length trace."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.launch import steps as steps_mod
from repro.serve import (
    EngineConfig,
    Request,
    Scheduler,
    ServeEngine,
    default_buckets,
    empty_row_like,
    init_pool,
    make_sampler,
    reset_slot,
    write_slot,
)
from repro.serve.pool import UNWRITTEN_POS


def _params(cfg, seed=0):
    mod = steps_mod.model_module(cfg)
    return mod.init(cfg, jax.random.PRNGKey(seed))


def _prompt(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab, size=n).astype(np.int32)


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------

def test_default_buckets_cover_max_len():
    assert default_buckets(96) == (16, 32, 64, 96)
    assert default_buckets(64) == (16, 32, 64)


def test_scheduler_bucket_rounding():
    s = Scheduler(2, (16, 32, 64))
    assert s.bucket_for(1) == 16
    assert s.bucket_for(16) == 16
    assert s.bucket_for(17) == 32
    assert s.bucket_for(100) == 100          # beyond largest: exact
    exact = Scheduler(2, (16, 32), exact=True)
    assert exact.bucket_for(17) == 17        # recurrent families


def test_scheduler_admission_and_reuse():
    s = Scheduler(2, (16,))
    for i in range(5):
        s.submit(Request(i, np.zeros(4, np.int32)))
    got = s.admit()
    assert [r.rid for _, r in got] == [0, 1]
    assert s.admit() == []                   # no free slot
    assert s.n_queued == 3
    slot0 = got[0][0]
    s.release(slot0)
    got2 = s.admit()
    assert len(got2) == 1
    assert got2[0][0] == slot0               # freed slot is reused
    assert got2[0][1].rid == 2               # FIFO order


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------

def test_sampler_greedy_and_topk():
    logits = jnp.asarray([[0.0, 5.0, 1.0, -2.0]] * 3)
    key = jax.random.PRNGKey(0)
    assert np.all(np.asarray(make_sampler("greedy")(logits, key)) == 1)
    # top_k=1 must degenerate to greedy regardless of temperature
    tk = make_sampler("top_k", temperature=5.0, top_k=1)
    assert np.all(np.asarray(tk(logits, key)) == 1)
    # top_k=2 only ever emits the two best ids
    tk2 = make_sampler("top_k", temperature=2.0, top_k=2)
    for s in range(5):
        got = np.asarray(tk2(logits, jax.random.PRNGKey(s)))
        assert set(got.tolist()) <= {1, 2}


def test_sampler_rejects_bad_args():
    with pytest.raises(ValueError):
        make_sampler("nucleus")
    with pytest.raises(ValueError):
        make_sampler("temperature", temperature=0.0)
    with pytest.raises(ValueError):
        make_sampler("top_k", top_k=0)


# ---------------------------------------------------------------------------
# slot pool: insert / reset on real model caches
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["qwen2-0.5b", "falcon-mamba-7b"])
def test_pool_write_and_reset_slot(arch):
    cfg = get_smoke_config(arch)
    mod = steps_mod.model_module(cfg)
    S, slots = 16, 3
    pool = init_pool(cfg, slots, S)
    assert pool["idx"].shape == (slots,)

    params = _params(cfg)
    row = mod.init_cache(cfg, 1, S)
    length = 5
    logits, row = mod.prefill(
        cfg, params, {"tokens": jnp.asarray(_prompt(cfg, 8)[None])},
        row, length=jnp.asarray([length]))
    pool = write_slot(pool, 1, row, length)
    assert int(pool["idx"][1]) == length     # real length, not padded 8
    assert int(pool["idx"][0]) == 0

    if cfg.family == "dense":
        pos = np.asarray(pool["layers"]["pos"])   # (L, B, S)
        # inserted slot: first `length` columns live, padded tail masked
        assert np.all(pos[:, 1, :length] == np.arange(length))
        assert np.all(pos[:, 1, length:] == UNWRITTEN_POS)
        # untouched slots stay fully masked
        assert np.all(pos[:, 0, :] == UNWRITTEN_POS)
        k = np.asarray(pool["layers"]["k"])
        assert np.abs(k[:, 1, :length]).max() > 0
        assert np.all(k[:, 0] == 0)

    pool = reset_slot(pool, 1)
    assert int(pool["idx"][1]) == 0
    if cfg.family == "dense":
        pos = np.asarray(pool["layers"]["pos"])
        assert np.all(pos[:, 1, :] == UNWRITTEN_POS)
        assert np.all(np.asarray(pool["layers"]["k"])[:, 1] == 0)
    else:
        # recurrent state rows zeroed (additive state must not leak)
        h = np.asarray(jax.tree.leaves(pool["layers"])[0])
        assert np.all(h[:, 1] == 0)


def test_pool_write_reset_whisper_cache():
    """The slot APIs are family-generic: whisper's enc-dec cache
    (self KV + precomputed cross KV) round-trips through write/reset."""
    cfg = get_smoke_config("whisper-tiny")
    mod = steps_mod.model_module(cfg)
    S, enc_len, slots = 12, 6, 2
    pool = init_pool(cfg, slots, S, enc_len=enc_len)
    params = _params(cfg)
    row = mod.init_cache(cfg, 1, S, enc_len)
    batch = {"tokens": jnp.asarray(_prompt(cfg, 4)[None]),
             "enc_embeds": jnp.ones((1, enc_len, cfg.d_model),
                                    jnp.float32)}
    _, row = mod.prefill(cfg, params, batch, row,
                         length=jnp.asarray([4]))
    pool = mod.cache_write_slot(pool, 0, row, 4)
    assert int(pool["idx"][0]) == 4
    ck = np.asarray(pool["layers"]["cross_k"])   # (L, B, enc, h, hd)
    assert np.abs(ck[:, 0]).max() > 0
    assert np.all(ck[:, 1] == 0)
    pool = mod.cache_reset_slot(pool, 0)
    assert int(pool["idx"][0]) == 0
    assert np.all(np.asarray(pool["layers"]["cross_k"])[:, 0] == 0)
    pos = np.asarray(pool["layers"]["self"]["pos"])
    assert np.all(pos[:, 0] == UNWRITTEN_POS)


def test_empty_row_like_matches_fresh_cache():
    cfg = get_smoke_config("qwen2-0.5b")
    pool = init_pool(cfg, 2, 8)
    row = empty_row_like(pool)
    assert row["idx"].shape == ()
    assert row["layers"]["k"].shape[1] == 1
    assert np.all(np.asarray(row["layers"]["pos"]) == UNWRITTEN_POS)


# ---------------------------------------------------------------------------
# engine end-to-end
# ---------------------------------------------------------------------------

def _static_greedy(cfg, params, prompt, gen):
    """Reference: the legacy fixed-batch greedy decode."""
    mod = steps_mod.model_module(cfg)
    cache = mod.init_cache(cfg, 1, len(prompt) + gen)
    logits, cache = mod.prefill(
        cfg, params, {"tokens": jnp.asarray(prompt[None])}, cache)
    tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    out = [int(tok[0, 0])]
    for _ in range(gen - 1):
        logits, cache = mod.decode_step(cfg, params, tok, cache)
        tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        out.append(int(tok[0, 0]))
    return out


@pytest.mark.parametrize("bucket", [16, 32])
def test_engine_matches_static_greedy(bucket):
    """Slot-pool decode (vector idx, per-row cache writes, bucketed +
    padded prefill) reproduces the static path token-for-token. An
    empty slot rides along to prove inactive slots don't perturb
    active ones."""
    cfg = get_smoke_config("qwen2-0.5b")
    params = _params(cfg)
    prompt, gen = _prompt(cfg, 16, seed=1), 8
    ref = _static_greedy(cfg, params, prompt, gen)
    eng = ServeEngine(cfg, params, EngineConfig(
        max_slots=2, max_len=48, decode_chunk=3, buckets=(bucket,)))
    out = eng.run([Request(0, prompt, max_new_tokens=gen)])
    assert out[0].tokens == ref
    assert out[0].finish_reason == "length"


def test_engine_mixed_length_trace_with_slot_reuse():
    """More requests than slots, staggered arrivals, varying prompt and
    generation lengths: every request finishes with exactly its token
    budget and slots are reused across the trace."""
    cfg = get_smoke_config("qwen2-0.5b")
    params = _params(cfg)
    rng = np.random.default_rng(3)
    reqs = []
    for i, (tp, gen) in enumerate([(5, 6), (12, 3), (20, 7), (7, 1),
                                   (30, 5), (3, 4)]):
        reqs.append(Request(
            i, rng.integers(0, cfg.vocab, size=tp).astype(np.int32),
            max_new_tokens=gen))
    eng = ServeEngine(cfg, params, EngineConfig(
        max_slots=2, max_len=64, decode_chunk=4))
    out = eng.run(reqs, arrivals=[0, 0, 1, 2, 3, 4])
    assert sorted(out) == list(range(6))
    for r in reqs:
        assert len(out[r.rid].tokens) == r.max_new_tokens
        assert out[r.rid].finish_reason == "length"
    # 6 requests over 2 slots => slots were recycled
    assert eng.stats["prefills"] == 6
    assert eng.scheduler.n_free == 2
    assert eng.n_active == 0


def test_engine_eos_termination():
    """A request stops at the first emission of its EOS token (taken
    from the 3rd greedy token of a free run, so at or before the 3rd
    token when a random-init model repeats itself); the independent
    co-resident request is unaffected."""
    cfg = get_smoke_config("qwen2-0.5b")
    params = _params(cfg)
    p0, p1 = _prompt(cfg, 10, seed=4), _prompt(cfg, 9, seed=5)
    probe = ServeEngine(cfg, params, EngineConfig(
        max_slots=2, max_len=32, decode_chunk=2))
    free_run = probe.run([Request(0, p0, max_new_tokens=6),
                          Request(1, p1, max_new_tokens=6)])
    eos = free_run[0].tokens[2]      # emitted on the 3rd decode of rid 0

    eng = ServeEngine(cfg, params, EngineConfig(
        max_slots=2, max_len=32, decode_chunk=2))
    out = eng.run([Request(0, p0, max_new_tokens=6, eos_id=int(eos)),
                   Request(1, p1, max_new_tokens=6)])
    first = free_run[0].tokens.index(eos)
    assert out[0].finish_reason == "eos"
    assert out[0].tokens == free_run[0].tokens[:first + 1]
    assert out[0].tokens[-1] == eos
    assert out[1].tokens == free_run[1].tokens   # neighbor unaffected


def test_engine_decode_is_single_program():
    """The decode inner loop must be one jitted program per chunk, not
    per-token Python dispatch: generating N tokens takes ceil(N/chunk)
    decode dispatches."""
    cfg = get_smoke_config("qwen2-0.5b")
    params = _params(cfg)
    eng = ServeEngine(cfg, params, EngineConfig(
        max_slots=1, max_len=32, decode_chunk=5))
    out = eng.run([Request(0, _prompt(cfg, 8), max_new_tokens=11)])
    assert len(out[0].tokens) == 11
    # 10 post-prefill tokens at 5 tokens/program = 2 chunk dispatches
    assert eng.stats["decode_chunks"] == 2


def test_engine_validates_requests():
    cfg = get_smoke_config("qwen2-0.5b")
    params = _params(cfg)
    eng = ServeEngine(cfg, params, EngineConfig(max_slots=1, max_len=16))
    with pytest.raises(ValueError):
        eng.submit(Request(0, _prompt(cfg, 12), max_new_tokens=8))
    with pytest.raises(ValueError):
        eng.submit(Request(0, _prompt(cfg, 4), max_new_tokens=0))
    with pytest.raises(NotImplementedError):
        ServeEngine(get_smoke_config("whisper-tiny"), {}, EngineConfig())


def test_engine_hybrid_family_matches_static():
    """hybrid (recurrentgemma pattern: rglru states + windowed-attn
    rings) through the slot pool matches the static path."""
    cfg = get_smoke_config("recurrentgemma-9b")
    params = _params(cfg)
    prompt, gen = _prompt(cfg, 7, seed=8), 5
    ref = _static_greedy(cfg, params, prompt, gen)
    eng = ServeEngine(cfg, params, EngineConfig(
        max_slots=2, max_len=12, decode_chunk=2))
    out = eng.run([Request(0, prompt, max_new_tokens=gen),
                   Request(1, _prompt(cfg, 5, seed=9),
                           max_new_tokens=3)])
    assert out[0].tokens == ref
    assert len(out[1].tokens) == 3


def test_engine_recurrent_family_ssm():
    """ssm caches are recurrent state, not KV — but padded (bucketed)
    prefill is safe now that the mixers gather their carried state at
    the real prompt boundary (``state_len``), so ssm shares the
    bucketed prefill programs. An 11-token prompt rides the 16 bucket
    and must still match the exact static path token-for-token."""
    cfg = get_smoke_config("falcon-mamba-7b")
    params = _params(cfg)
    prompt, gen = _prompt(cfg, 11, seed=6), 5
    ref = _static_greedy(cfg, params, prompt, gen)
    eng = ServeEngine(cfg, params, EngineConfig(
        max_slots=2, max_len=32, decode_chunk=2, buckets=(16,)))
    assert not eng.scheduler.exact       # only hybrid needs exactness
    assert eng.scheduler.bucket_for(len(prompt)) == 16
    out = eng.run([Request(0, prompt, max_new_tokens=gen),
                   Request(1, _prompt(cfg, 7, seed=7),
                           max_new_tokens=3)])
    assert out[0].tokens == ref
    assert len(out[1].tokens) == 3


def test_ssm_right_padded_prefill_state_exact():
    """Regression (padded-prefill recurrent-state bug): a right-padded
    ssm prefill used to return the carried state at the padded tail —
    conv window over pad junk, scan state past the boundary — which
    write_slot copied verbatim into the pool. The state for a padded
    prompt must equal the state of the exact-length prefill bitwise."""
    cfg = get_smoke_config("falcon-mamba-7b")
    mod = steps_mod.model_module(cfg)
    params = _params(cfg)
    tp, bucket = 11, 16
    prompt = _prompt(cfg, tp, seed=12)

    exact = mod.init_cache(cfg, 1, 32)
    lg_e, exact = mod.prefill(
        cfg, params, {"tokens": jnp.asarray(prompt[None])}, exact,
        length=jnp.asarray([tp]))
    padded_toks = np.zeros((1, bucket), np.int32)
    padded_toks[0, :tp] = prompt
    padded = mod.init_cache(cfg, 1, 32)
    lg_p, padded = mod.prefill(
        cfg, params, {"tokens": jnp.asarray(padded_toks)}, padded,
        length=jnp.asarray([tp]))

    np.testing.assert_allclose(np.asarray(lg_e), np.asarray(lg_p),
                               rtol=0, atol=0)
    for le, lp in zip(jax.tree.leaves(exact["layers"]),
                      jax.tree.leaves(padded["layers"])):
        np.testing.assert_array_equal(np.asarray(le), np.asarray(lp))


# ---------------------------------------------------------------------------
# top-k under ties (regression) + the int8 serving tier (repro.lowp)
# ---------------------------------------------------------------------------

def test_sampler_topk_tied_logits_regression():
    """Regression: the top-k mask used to be a >= threshold on the
    k-th value, so ties *at* the threshold inflated the candidate set
    beyond k. With 4 ids tied at the max and top_k=2, only the two
    ids lax.top_k actually ranks first may ever be sampled."""
    logits = jnp.asarray([[3.0, 3.0, 3.0, 3.0, 0.0, -1.0]])
    vals, idx = jax.lax.top_k(logits, 2)
    allowed = set(np.asarray(idx[0]).tolist())
    assert len(allowed) == 2
    tk = make_sampler("top_k", temperature=1.0, top_k=2)
    seen = set()
    for s in range(64):
        seen.add(int(np.asarray(
            tk(logits, jax.random.PRNGKey(s)))[0]))
    assert seen <= allowed
    assert len(seen) == 2  # both survivors are reachable


def test_sampler_topk_ties_below_threshold():
    """Ties below the cut don't leak in either: k=3 with five ids
    sharing the 3rd-best value samples only ids lax.top_k keeps."""
    logits = jnp.asarray([[5.0, 4.0, 1.0, 1.0, 1.0, 1.0, 1.0, 0.0]])
    _, idx = jax.lax.top_k(logits, 3)
    allowed = set(np.asarray(idx[0]).tolist())
    tk = make_sampler("top_k", temperature=2.0, top_k=3)
    for s in range(48):
        assert int(np.asarray(
            tk(logits, jax.random.PRNGKey(s)))[0]) in allowed


def test_engine_int8_greedy_parity_and_memory():
    """The int8 serving tier: on a briefly-trained checkpoint every
    greedy request whose fp32 decision margin clears the quantization
    floor matches the fp32 engine token-for-token (weights AND the
    int8 KV cache in the decode path), and the resident memory drops.

    Random-init parity would be a coin flip — near-flat logits put
    every margin inside the int8 perturbation — so the harness trains
    first; see repro.lowp.serve_parity."""
    from repro.lowp import serve_greedy_parity

    r = serve_greedy_parity(train_steps=30)
    assert r["decided_total"] >= 2, r
    assert r["decided_matched"] == r["decided_total"], r
    # sub-floor prompts may flip, but not many at smoke scale
    assert r["matched"] >= r["total"] - 2, r
    # weights: all matmul leaves int8 (embedding stays fp32);
    # KV pool: codes int8 + per-position scales
    assert r["param_reduction"] > 2.0, r
    assert r["pool_reduction"] > 1.3, r


def test_engine_int8_quantized_residency():
    """EngineConfig(quant='int8') actually keeps int8 resident state:
    QTensor weight leaves and int8 KV code leaves with scale siblings
    (not fp32 tensors quantized on the fly)."""
    from repro.lowp import QTensor

    cfg = get_smoke_config("qwen2-0.5b")
    params = _params(cfg)
    eng = ServeEngine(cfg, params, EngineConfig(
        max_slots=2, max_len=32, decode_chunk=2, quant="int8"))
    qleaves = [l for l in jax.tree.leaves(
        eng.params, is_leaf=lambda l: isinstance(l, QTensor))
        if isinstance(l, QTensor)]
    assert qleaves and all(l.q.dtype == jnp.int8 for l in qleaves)
    layer0 = eng._pool["layers"]
    kv_names = [k for k in layer0 if k.split("/")[-1] in ("k", "v")]
    assert kv_names
    for k in kv_names:
        assert layer0[k].dtype == jnp.int8
        assert layer0[k + "_scale"].dtype == jnp.float32
    # and it still serves a trace
    out = eng.run([Request(0, _prompt(cfg, 9, seed=3),
                           max_new_tokens=4)])
    assert len(out[0].tokens) == 4

    with pytest.raises(ValueError):
        ServeEngine(cfg, params, EngineConfig(quant="int4"))


def test_engine_refuses_latent_attention():
    """The engine refuses an MLA model, naming the latent cache it would
    need."""
    from repro.serve.engine import EngineConfig, ServeEngine

    cfg = get_smoke_config("moonlight-16b-a3b")
    with pytest.raises(NotImplementedError, match="latent cache"):
        ServeEngine(cfg, {}, EngineConfig())
