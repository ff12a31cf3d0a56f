"""Continuous-batching serving engine.

Replaces the host-driven one-token-at-a-time serving loop with three
pieces (the JetStream/vLLM decomposition, on this repo's cache APIs):

* **Slot pool** (:mod:`repro.serve.pool`): the decode cache is a
  ``(max_slots, ...)`` array family; finished requests free their slot
  and new requests join mid-flight — no recompilation, ever, because
  every pool operation is a dynamic-slice update at a traced slot id.
* **Scheduler**: FIFO admission queue + length-bucketed prefill.
  Prompts are right-padded to a small set of bucket lengths so prefill
  hits a handful of compiled programs; the padded tail is re-masked at
  insert so it is never attended. Recurrent families (ssm/hybrid) use
  exact-length prefill — padding would pollute their carried state.
* **Jitted decode loop**: ``decode_chunk`` steps run as ONE program — a
  ``lax.scan`` over the model's single-token decode with on-device
  sampling (greedy / temperature / top-k), per-slot termination
  (max-token budget + EOS) and an active-slot mask. The host only
  touches tokens at chunk boundaries, where it harvests finished
  requests and admits queued ones.

The engine is model-generic over the LM families whose prompt batch is
token-only (dense / moe / ssm / hybrid). VLM and audio requests need
modality-specific prefill inputs and are out of scope here (the pool
APIs themselves are family-generic and cover whisper's cache).
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.obs import NULL as NULL_OBS
from repro.serve import pool as pool_mod
from repro.serve.sampling import make_sampler


# ---------------------------------------------------------------------------
# Requests
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Request:
    """One generation request."""

    rid: int
    prompt: np.ndarray              # (Tp,) int32 token ids
    max_new_tokens: int = 16
    eos_id: int = -1                # -1: no EOS termination


@dataclasses.dataclass
class FinishedRequest:
    rid: int
    prompt: np.ndarray
    tokens: List[int]               # generated ids (EOS included if hit)
    finish_reason: str              # "length" | "eos"
    ttft_s: float = float("nan")    # submit -> first sampled token


@dataclasses.dataclass
class _SlotState:
    """Host-side record of the request occupying a slot."""

    req: Request
    tokens: List[int]
    ttft_s: float = float("nan")


# ---------------------------------------------------------------------------
# Scheduler
# ---------------------------------------------------------------------------

def default_buckets(max_len: int, lo: int = 16) -> Tuple[int, ...]:
    """Power-of-two prefill buckets up to ``max_len``."""
    out, b = [], lo
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return tuple(out)


def synthetic_trace(vocab: int, n: int, prompt_len: int, gen: int,
                    max_slots: int, seed: int = 0):
    """Synthetic mixed-length request trace (shared by the CLI driver
    and the throughput benchmark, so both measure the same workload):
    prompt lengths in [prompt_len//2, prompt_len], budgets in
    [gen//2, gen], arrivals staggered one wave per ``max_slots`` so
    requests join and finish mid-flight. Returns (requests, arrivals)."""
    rng = np.random.default_rng(seed)
    reqs, arrivals = [], []
    for i in range(n):
        tp = int(rng.integers(max(prompt_len // 2, 1), prompt_len + 1))
        g = int(rng.integers(max(gen // 2, 1), gen + 1))
        reqs.append(Request(
            i, rng.integers(0, vocab, size=tp).astype(np.int32),
            max_new_tokens=g))
        arrivals.append(i // max(max_slots, 1))
    return reqs, arrivals


class Scheduler:
    """Admission queue + slot bookkeeping + prefill length buckets."""

    def __init__(self, max_slots: int, buckets: Sequence[int],
                 exact: bool = False):
        self.queue: collections.deque = collections.deque()
        self.free: List[int] = list(range(max_slots))[::-1]
        self.buckets = tuple(sorted(buckets))
        self.exact = exact

    def bucket_for(self, n: int) -> int:
        """Compiled prefill length for an ``n``-token prompt."""
        if self.exact:
            return n
        for b in self.buckets:
            if b >= n:
                return b
        return n

    def submit(self, req: Request) -> None:
        self.queue.append(req)

    def admit(self, can_admit=None) -> List[Tuple[int, Request]]:
        """Pop (slot, request) pairs while both a free slot and a queued
        request exist. ``can_admit(req)`` adds a resource predicate
        beyond the free slot (the paged engine's "free blocks >= prompt
        need"); admission is FIFO, so a blocked queue head blocks the
        queue (no head-of-line bypass — determinism over utilization)."""
        out = []
        while self.queue and self.free:
            if can_admit is not None and not can_admit(self.queue[0]):
                break
            out.append((self.free.pop(), self.queue.popleft()))
        return out

    def release(self, slot: int) -> None:
        self.free.append(slot)

    @property
    def n_queued(self) -> int:
        return len(self.queue)

    @property
    def n_free(self) -> int:
        return len(self.free)


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class EngineConfig:
    max_slots: int = 8
    max_len: int = 256              # per-slot cache columns
    decode_chunk: int = 8           # tokens per jitted decode program
    method: str = "greedy"          # greedy | temperature | top_k
    temperature: float = 1.0
    top_k: int = 0
    buckets: Optional[Tuple[int, ...]] = None
    seed: int = 0
    # "int8": resident weights + KV cache stored as int8 codes with
    # fp32 scales (repro.lowp.serve_quant); dequant runs inside the
    # jitted programs, fused into the consuming matmuls
    quant: str = "none"


class ServeEngine:
    def __init__(self, cfg, params, ecfg: EngineConfig, mesh=None,
                 obs=None):
        if cfg.family in ("vlm", "audio"):
            raise NotImplementedError(
                f"{cfg.family} requests need modality inputs at prefill; "
                "the continuous-batching engine currently serves "
                "token-only prompt families (dense/moe/ssm/hybrid)")
        from repro.launch import steps as steps_mod
        from repro.models import lm

        lm.check_serving(cfg)
        if ecfg.quant not in ("none", "int8"):
            raise ValueError(f"unknown quant mode {ecfg.quant!r}; "
                             "one of ('none', 'int8')")
        self.cfg = cfg
        self.ecfg = ecfg
        self._quant = ecfg.quant == "int8"
        if self._quant:
            from repro.lowp import serve_quant
            self._sq = serve_quant
            # resident weights: int8 codes + per-channel scales
            params = jax.jit(serve_quant.quantize_params)(params)
        self.params = params
        self.mod = steps_mod.model_module(cfg)
        self.mesh = mesh

        self._pool = self._build_pool()
        B = ecfg.max_slots
        self._tok = jnp.zeros((B, 1), jnp.int32)
        self._active = jnp.zeros((B,), bool)
        self._remaining = jnp.zeros((B,), jnp.int32)
        self._eos = jnp.full((B,), -1, jnp.int32)
        self._key = jax.random.PRNGKey(ecfg.seed)

        # hybrid's windowed ring requires slot column c == position c, so
        # its prompts prefill at exact length; padded prefill elsewhere is
        # safe — attention re-masks pad columns, and recurrent mixers
        # gather their carried state at the real boundary (state_len)
        exact = cfg.family == "hybrid"
        self.scheduler = Scheduler(
            ecfg.max_slots, ecfg.buckets or default_buckets(ecfg.max_len),
            exact=exact)
        self._slots: Dict[int, _SlotState] = {}
        self._finished: List[FinishedRequest] = []
        self._t_submit: Dict[int, float] = {}

        self._sampler = make_sampler(ecfg.method, ecfg.temperature,
                                     ecfg.top_k)
        self._sample1 = jax.jit(self._sampler)
        self._build_programs()

        self.stats: Dict[str, Any] = {}
        self.reset_stats()
        self._obs = obs if obs is not None else NULL_OBS
        self._init_obs_handles()

    def set_obs(self, obs) -> None:
        """(Re)bind the observability sink — the CLI driver attaches
        the real one *after* warmup, so TTFT/TPOT histograms hold
        steady-state numbers only (mirrors ``reset_stats``)."""
        self._obs = obs if obs is not None else NULL_OBS
        self._init_obs_handles()

    def _init_obs_handles(self) -> None:
        """Metric handles held once; per-token cost when obs is off is
        one ``enabled`` attribute read per chunk boundary."""
        o = self._obs
        if not o.enabled:
            return
        self._h_ttft = o.histogram(
            "serve_ttft_s", "submit -> first sampled token")
        self._h_tpot = o.histogram(
            "serve_tpot_s", "decode-chunk wall / tokens emitted")
        self._h_chunk = o.histogram(
            "serve_decode_chunk_s", "jitted decode-chunk wall")
        self._h_prefill = o.histogram(
            "serve_prefill_s", "per-admission prefill wall")
        self._c_req = o.counter(
            "serve_requests_total", "requests submitted")
        self._c_fin = o.counter(
            "serve_finished_total", "requests finished, by reason")
        self._c_tok = o.counter(
            "serve_tokens_total", "decode tokens emitted")
        self._g_queue = o.gauge(
            "serve_queue_depth", "requests waiting for a slot")
        self._g_occ = o.gauge(
            "serve_slot_occupancy", "active slots / max_slots")

    def _build_pool(self):
        """Allocate the resident KV pool (subclass hook: the paged
        engine builds a block pool instead of dense slot stripes)."""
        pool = pool_mod.init_pool(self.cfg, self.ecfg.max_slots,
                                  self.ecfg.max_len)
        if self._quant:
            # resident KV: int8 codes + sibling *_scale leaves (the
            # pool machinery resolves those names to the same slot axis
            # as their parent, so write/reset ride unchanged)
            pool = jax.jit(self._sq.quantize_kv)(pool)
        if self.mesh is not None:
            from repro.dist import sharding as shard_rules
            pool = jax.device_put(
                pool, shard_rules.pool_sharding(pool, self.mesh))
        return pool

    def _build_programs(self) -> None:
        """Build the engine's jitted programs (subclass hook)."""
        # one jitted prefill; jax's shape-keyed cache gives one compiled
        # program per (bucket length) — exactly the scheduler's bucket set
        self._prefill = jax.jit(self._make_prefill())
        self._decode = jax.jit(self._make_decode_chunk(),
                               donate_argnums=(1, 2, 3, 4, 6))
        self._admit = jax.jit(self._make_admit(),
                              donate_argnums=(0, 1, 2, 3, 4))
        empty = pool_mod.empty_row_like(self._pool)
        self._reset = jax.jit(
            lambda p, s: pool_mod.reset_slot(p, s, empty),
            donate_argnums=(0,))

    def reset_stats(self) -> None:
        """Zero counters + drop finished-request records (e.g. after a
        warmup pass, so timed numbers are steady-state only)."""
        self._finished.clear()
        self.stats.clear()
        self.stats.update({"prefills": 0, "decode_chunks": 0,
                           "decode_tokens": 0, "prefill_tokens": 0,
                           "prefill_s": 0.0, "decode_s": 0.0})

    # -- jitted program builders -------------------------------------------

    def _make_prefill(self):
        cfg, mod, max_len = self.cfg, self.mod, self.ecfg.max_len
        quant = self._quant

        def prefill_one(params, tokens, length):
            if quant:
                params = self._sq.dequantize_params(params)
            cache = mod.init_cache(cfg, 1, max_len)
            logits, cache = mod.prefill(
                cfg, params, {"tokens": tokens}, cache,
                length=length[None])
            return logits, cache

        return prefill_one

    def _make_admit(self):
        quant = self._quant

        def admit(pool, tok, active, remaining, eos_ids, slot, row,
                  length, first_tok, n_remaining, eos_id):
            if quant:
                # the prefill row is float; encode it into the resident
                # int8 + scales layout before the slot write
                row = self._sq.quantize_kv(row)
            pool = pool_mod.write_slot(pool, slot, row, length)
            tok = jax.lax.dynamic_update_slice(
                tok, first_tok.reshape(1, 1), (slot, 0))
            hit_eos = (first_tok == eos_id) & (eos_id >= 0)
            alive = (n_remaining > 0) & ~hit_eos
            active = jax.lax.dynamic_update_slice(
                active, alive[None], (slot,))
            remaining = jax.lax.dynamic_update_slice(
                remaining, n_remaining[None], (slot,))
            eos_ids = jax.lax.dynamic_update_slice(
                eos_ids, eos_id[None], (slot,))
            return pool, tok, active, remaining, eos_ids

        return admit

    def _make_decode_chunk(self):
        cfg, mod = self.cfg, self.mod
        sampler = self._sampler
        chunk = self.ecfg.decode_chunk
        max_len = self.ecfg.max_len

        quant = self._quant
        # dense/moe: divert inactive slots' writes past the cache edge
        # (idx -> max_len drops on the per-row scatter). This keeps idle
        # slots' columns bitwise untouched, so the set of slots written
        # in a chunk is exactly the chunk-entry active set — which is
        # what lets int8 mode requantize only dirty slots. hybrid's
        # ring write is modular in idx and cannot be diverted this way.
        mask_idle = cfg.family in ("dense", "moe")

        def decode_chunk(params, pool, tok, active, remaining, eos_ids,
                         key):
            """``chunk`` model steps + sampling + termination as one
            program. Inactive slots keep stepping on their last token
            with their writes dropped (dense/moe) or landing in freed
            columns healed by the next ``write_slot`` (hybrid/ssm);
            ``emitted`` records which scan iterations produced a real
            token per slot.

            In int8 mode the weights are dequantized once per chunk and
            the KV pool once per chunk boundary: the scan carries the
            float pool (fp32 dequant is exact on the codes), and the
            chunk's last state is re-encoded into the resident int8
            layout for the slots written this chunk only — untouched
            slots carry their codes bitwise (repro.lowp.serve_quant)."""
            qpool = pool
            dirty = active                       # chunk-entry active set
            if quant:
                params = self._sq.dequantize_params(params)
                pool = self._sq.dequantize_kv(pool)

            def body(carry, _):
                pool, tok, active, remaining, key = carry
                step_pool = pool
                if mask_idle:
                    step_pool = dict(pool)
                    step_pool["idx"] = jnp.where(active, pool["idx"],
                                                 max_len)
                logits, new_pool = mod.decode_step(cfg, params, tok,
                                                   step_pool)
                # keep the pool's declared dtypes across the scan carry
                # (e.g. mamba's conv state is returned in compute dtype)
                pool = jax.tree.map(
                    lambda n, o: n.astype(o.dtype), new_pool, pool)
                key, sub = jax.random.split(key)
                nxt = sampler(logits, sub)
                nxt = jnp.where(active, nxt, tok[:, 0])
                emitted = active
                remaining = remaining - active.astype(jnp.int32)
                hit_eos = (nxt == eos_ids) & (eos_ids >= 0)
                active = active & (remaining > 0) & ~hit_eos
                return ((pool, nxt[:, None], active, remaining, key),
                        (nxt, emitted))

            carry, (toks, emitted) = jax.lax.scan(
                body, (pool, tok, active, remaining, key), None,
                length=chunk)
            pool, tok, active, remaining, key = carry
            if quant:
                pool = self._sq.requantize_kv(
                    pool, like=qpool,
                    dirty=dirty if mask_idle else None)
            return pool, tok, active, remaining, key, toks, emitted

        return decode_chunk

    # -- public API --------------------------------------------------------

    def resident_bytes(self) -> Dict[str, int]:
        """Bytes of the resident weight tree and KV pool (int8 mode
        counts codes + scales) — the serve-memory number
        ``benchmarks/precision_ladder.py`` reports."""
        from repro.lowp.serve_quant import tree_bytes
        return {"params": tree_bytes(self.params),
                "pool": tree_bytes(self._pool)}

    def submit(self, req: Request) -> None:
        tp = len(req.prompt)
        if req.max_new_tokens < 1:
            raise ValueError(
                f"request {req.rid}: max_new_tokens must be >= 1 (the "
                "first token is sampled from the prefill logits)")
        if tp + req.max_new_tokens > self.ecfg.max_len:
            raise ValueError(
                f"request {req.rid}: prompt ({tp}) + max_new_tokens "
                f"({req.max_new_tokens}) exceeds max_len "
                f"({self.ecfg.max_len})")
        if self.cfg.family == "hybrid" and self.cfg.window \
                and tp > self.cfg.window:
            raise ValueError(
                f"request {req.rid}: prompt ({tp}) exceeds the local-"
                f"attention ring ({self.cfg.window}); slot columns and "
                "positions would no longer be identity-mapped")
        self._t_submit[req.rid] = time.monotonic()
        self.scheduler.submit(req)
        if self._obs.enabled:
            self._c_req.inc()
            self._g_queue.set(self.scheduler.n_queued)

    @property
    def n_active(self) -> int:
        return len(self._slots)

    def _do_admissions(self) -> None:
        for slot, req in self.scheduler.admit():
            t0 = time.monotonic()
            tp = len(req.prompt)
            bucket = self.scheduler.bucket_for(tp)
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :tp] = req.prompt
            logits, row = self._prefill(
                self.params, jnp.asarray(toks),
                jnp.asarray(tp, jnp.int32))
            self._key, sub = jax.random.split(self._key)
            first = self._sample1(logits, sub)[0]
            (self._pool, self._tok, self._active, self._remaining,
             self._eos) = self._admit(
                self._pool, self._tok, self._active, self._remaining,
                self._eos, slot, row, jnp.asarray(tp, jnp.int32), first,
                jnp.asarray(req.max_new_tokens - 1, jnp.int32),
                jnp.asarray(req.eos_id, jnp.int32))
            now = time.monotonic()
            ttft = now - self._t_submit.pop(req.rid, t0)
            self._slots[slot] = _SlotState(req, [int(first)], ttft)
            self.stats["prefills"] += 1
            self.stats["prefill_tokens"] += bucket
            self.stats["prefill_s"] += now - t0
            if self._obs.enabled:
                self._h_prefill.observe(now - t0)
                self._h_ttft.observe(ttft)

    def _release_slot(self, slot: int) -> None:
        """Return a finished slot's resources (subclass hook: the paged
        engine reclaims its table's blocks here)."""
        self._pool = self._reset(self._pool, jnp.asarray(slot))
        self.scheduler.release(slot)

    def _harvest(self) -> List[FinishedRequest]:
        done = []
        active = np.asarray(self._active)
        for slot in sorted(self._slots):
            if active[slot]:
                continue
            st = self._slots.pop(slot)
            reason = "eos" if (st.req.eos_id >= 0 and st.tokens
                               and st.tokens[-1] == st.req.eos_id) \
                else "length"
            done.append(FinishedRequest(st.req.rid, st.req.prompt,
                                        st.tokens, reason, st.ttft_s))
            self._release_slot(slot)
            if self._obs.enabled:
                self._c_fin.inc(reason=reason)
                self._obs.write({
                    "kind": "request_finished", "rid": st.req.rid,
                    "reason": reason, "ttft_s": st.ttft_s,
                    "n_tokens": len(st.tokens)})
        self._finished.extend(done)
        return done

    def _pre_decode(self) -> None:
        """Hook run before each decode-chunk dispatch (the paged engine
        grows block tables for the coming chunk here, with backpressure
        when the free-list runs dry)."""

    def step(self) -> List[FinishedRequest]:
        """One engine iteration: admit -> decode one chunk -> harvest.
        Returns the requests that finished this iteration."""
        self._do_admissions()
        if not self._slots:
            return self._harvest()
        # some admissions can finish immediately (max_new_tokens == 1 /
        # EOS on the first token): free those slots before decoding
        done = self._harvest()
        if not self._slots:
            return done
        self._pre_decode()
        if not self._slots:      # backpressure may have preempted all
            return done + self._harvest()
        t0 = time.monotonic()
        self._key, sub = jax.random.split(self._key)
        with self._obs.span("decode_chunk", cat="serve"):
            (self._pool, self._tok, self._active, self._remaining, sub,
             toks, emitted) = self._decode(
                self.params, self._pool, self._tok, self._active,
                self._remaining, self._eos, sub)
            toks = np.asarray(toks)              # (chunk, B) -- syncs
        emitted = np.asarray(emitted)
        dt = time.monotonic() - t0
        self.stats["decode_chunks"] += 1
        self.stats["decode_s"] += dt
        n_emitted = 0
        for slot, st in self._slots.items():
            got = toks[emitted[:, slot], slot]
            st.tokens.extend(int(t) for t in got)
            n_emitted += int(emitted[:, slot].sum())
        self.stats["decode_tokens"] += n_emitted
        if self._obs.enabled:
            self._h_chunk.observe(dt)
            if n_emitted:
                self._c_tok.inc(n_emitted)
                self._h_tpot.observe(dt / n_emitted)
            self._g_queue.set(self.scheduler.n_queued)
            self._g_occ.set(len(self._slots) / self.ecfg.max_slots)
        return done + self._harvest()

    def run(self, requests: Sequence[Request],
            arrivals: Optional[Sequence[int]] = None,
            max_steps: int = 10_000) -> Dict[int, FinishedRequest]:
        """Drive a whole trace: ``arrivals[i]`` is the engine step at
        which ``requests[i]`` is submitted (default: all at step 0).
        Returns {rid: FinishedRequest}."""
        arrivals = list(arrivals or [0] * len(requests))
        if len(arrivals) != len(requests):
            raise ValueError("arrivals and requests length mismatch")
        pending = sorted(zip(arrivals, range(len(requests))),
                         key=lambda p: p[0])
        out: Dict[int, FinishedRequest] = {}
        step_i = 0
        while pending or self.scheduler.n_queued or self._slots:
            while pending and pending[0][0] <= step_i:
                _, i = pending.pop(0)
                self.submit(requests[i])
            for fin in self.step():
                out[fin.rid] = fin
            step_i += 1
            if step_i > max_steps:
                raise RuntimeError("engine did not drain the trace "
                                   f"within {max_steps} steps")
        return out
