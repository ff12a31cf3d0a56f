"""Serving driver: continuous-batching engine (default) or the legacy
single-static-batch path (``--static``).

CPU quickstart (reduced config, real tokens; drop ``--smoke`` and
``JAX_PLATFORMS=cpu`` on a TPU host for the published widths):

  # continuous batching over a synthetic mixed-length request trace
  JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.serve \
      --arch qwen2-0.5b --smoke --requests 6 --max-slots 2 \
      --prompt-len 24 --gen 8

  # legacy fixed-batch prefill+decode (baseline / A-B reference)
  JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.serve \
      --arch qwen2-0.5b --smoke --static --batch 4 --prompt-len 32 \
      --gen 16

Both paths sample on device (greedy by default; ``--no-greedy`` enables
``--temperature``/``--top-k`` sampling) and warm up the jitted programs
before the timed section, so ``decode_tok_per_s`` is steady-state
execution, not compile time. The decode shapes of the assignment grid
(``decode_32k`` / ``long_500k``) lower exactly the ``decode_step``
jitted here (see launch/steps.py).
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro import obs as obs_mod
from repro.configs import get_config, get_smoke_config
from repro.data import SyntheticTokens
from repro.dist import sharding as shard_rules
from repro.launch import compile_cache
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_dev_mesh
from repro.models import lm
from repro.serve import (
    EngineConfig,
    PagedConfig,
    PagedServeEngine,
    Request,
    ServeEngine,
    synthetic_trace,
)
from repro.serve.sampling import make_sampler


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--static", action="store_true",
                    help="legacy fixed-batch path (no continuous "
                         "batching)")
    ap.add_argument("--batch", type=int, default=4,
                    help="static path: fixed batch size")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    # --greedy used to be store_true with default=True: a dead flag.
    # Now a real toggle: --no-greedy switches to stochastic sampling.
    ap.add_argument("--greedy", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="greedy decoding (default); --no-greedy "
                         "samples with --temperature / --top-k")
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--top-k", type=int, default=0,
                    help="with --no-greedy: restrict sampling to the "
                         "top-k logits (0 = full distribution)")
    ap.add_argument("--warmup", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="compile+run each program once before timing "
                         "(steady-state numbers); --no-warmup restores "
                         "the old cold-start timing")
    # engine path
    ap.add_argument("--quant", choices=("none", "int8"),
                    default="none",
                    help="engine path: resident weight + KV cache "
                         "precision (int8: per-channel weight scales, "
                         "per-position KV scales — repro.lowp)")
    ap.add_argument("--requests", type=int, default=8,
                    help="engine path: synthetic trace size")
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--decode-chunk", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=0,
                    help="engine pool columns (0: prompt-len + gen)")
    # paged engine path
    ap.add_argument("--paged", action="store_true",
                    help="engine path: block-paged KV pool "
                         "(repro.serve.paged) — dense/moe only")
    ap.add_argument("--block-len", type=int, default=16,
                    help="--paged: positions per KV block "
                         "(max-len must be a multiple)")
    ap.add_argument("--kv-blocks", type=int, default=0,
                    help="--paged: physical pool blocks (0: "
                         "max-slots * max-len / block-len, i.e. the "
                         "slot engine's footprint)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="--paged: shared-prefix cache (repeated "
                         "prompt prefixes prefill once, blocks are "
                         "refcount-shared copy-on-write)")
    # observability (repro.obs)
    ap.add_argument("--obs", action="store_true",
                    help="enable the telemetry spine: TTFT/TPOT/queue/"
                         "occupancy metrics, spans, console summary")
    ap.add_argument("--obs-dir", default=None,
                    help="write JSONL events + Prometheus snapshot + "
                         "Chrome trace here (implies --obs)")
    ap.add_argument("--obs-annotate", action="store_true",
                    help="also emit jax.profiler trace annotations "
                         "for spans")
    return ap


def sampling_args(args):
    if args.greedy:
        return {"method": "greedy", "temperature": 1.0, "top_k": 0}
    return {"method": "top_k" if args.top_k else "temperature",
            "temperature": args.temperature, "top_k": args.top_k}


def _trace(cfg, args):
    return synthetic_trace(cfg.vocab, args.requests, args.prompt_len,
                           args.gen, args.max_slots, seed=args.seed)


def serve_engine(cfg, args, mesh, obs=None):
    obs = obs if obs is not None else obs_mod.NULL
    mod = steps_mod.model_module(cfg)
    max_len = args.max_len or (args.prompt_len + args.gen)
    if args.paged:
        # the paged pool addresses whole blocks: round the column
        # budget up to a block multiple
        bl = args.block_len
        max_len = (max_len + bl - 1) // bl * bl
    with jax.set_mesh(mesh):
        params = mod.init(cfg, jax.random.PRNGKey(args.seed))
        params = jax.device_put(
            params, shard_rules.param_sharding(params, mesh))
        common = dict(max_slots=args.max_slots, max_len=max_len,
                      decode_chunk=args.decode_chunk, seed=args.seed,
                      quant=args.quant, **sampling_args(args))
        if args.paged:
            eng = PagedServeEngine(cfg, params, PagedConfig(
                block_len=args.block_len, n_blocks=args.kv_blocks,
                prefix_cache=args.prefix_cache, **common), mesh=mesh)
        else:
            eng = ServeEngine(cfg, params, EngineConfig(**common),
                              mesh=mesh)
        reqs, arrivals = _trace(cfg, args)
        if args.warmup:
            # compile the decode chunk + every prefill bucket the trace
            # will hit, off the clock (the engine's programs are
            # jit-cached per instance, so the warmup must run through
            # ``eng`` itself); warmup requests free their slots and
            # their stats are wiped before the timed run
            buckets = {eng.scheduler.bucket_for(len(r.prompt)): r
                       for r in reqs}
            warm = [Request(-1 - i, r.prompt, max_new_tokens=max(
                        1, min(args.decode_chunk + 1,
                               max_len - len(r.prompt))))
                    for i, r in enumerate(buckets.values())]
            with obs.span("serve_warmup"):
                eng.run(warm)
            eng.reset_stats()
        # attach the real sink only now: warmup compiles must not
        # pollute the steady-state latency histograms
        eng.set_obs(obs)
        t0 = time.monotonic()
        with obs.span("serve_trace", fence=lambda: eng._tok):
            done = eng.run(reqs, arrivals=arrivals)
            jax.block_until_ready(eng._tok)
        wall = time.monotonic() - t0
    n_tok = sum(len(f.tokens) for f in done.values())
    st = eng.stats
    summary = {
        "schema": 1,
        "kind": "serve_summary",
        "arch": cfg.name,
        "mode": "engine",
        "scheduler": {"queued": eng.scheduler.n_queued,
                      "free_slots": eng.scheduler.n_free},
        "sampling": sampling_args(args)["method"],
        "quant": args.quant,
        "resident_bytes": eng.resident_bytes(),
        "requests": len(done),
        "max_slots": args.max_slots,
        "decode_chunk": args.decode_chunk,
        "generated_tokens": n_tok,
        "wall_s": wall,
        "prefill_s": st["prefill_s"],
        "decode_s": st["decode_s"],
        "decode_tok_per_s": st["decode_tokens"] /
        max(st["decode_s"], 1e-9),
        "tok_per_s": n_tok / max(wall, 1e-9),
        "sample_tokens": done[0].tokens[:8] if 0 in done else [],
    }
    if args.paged:
        summary.update({
            "mode": "engine-paged",
            "block_len": args.block_len,
            "kv_blocks": eng._n_blocks,
            "prefill_tokens": st["prefill_tokens"],
            "prefix_hits": st["prefix_hits"],
            "prefix_hit_tokens": st["prefix_hit_tokens"],
            "preemptions": st["preemptions"],
            "evictions": st["evictions"],
            "free_blocks": eng.free_blocks,
            "free_blocks_low_watermark": eng._ledger.low_watermark,
        })
    if obs.enabled:
        rb = summary["resident_bytes"]
        obs.gauge("serve_resident_params_bytes",
                  "resident weight-tree bytes").set(rb["params"])
        obs.gauge("serve_resident_pool_bytes",
                  "resident KV pool bytes").set(rb["pool"])
    return summary, done


def serve_static(cfg, args, mesh):
    mod = steps_mod.model_module(cfg)
    total = args.prompt_len + args.gen
    sampler = make_sampler(**sampling_args(args))

    ds = SyntheticTokens(vocab=cfg.vocab, seq_len=args.prompt_len,
                         global_batch=args.batch, seed=args.seed)
    prompts = jnp.asarray(ds.batch_slice(0, 0, args.batch))
    batch = {"tokens": prompts}
    if cfg.family == "vlm":
        batch["img_embeds"] = jnp.zeros(
            (args.batch, cfg.n_img_tokens, cfg.vision_dim), jnp.float32)
        pos = jnp.broadcast_to(
            jnp.arange(args.prompt_len, dtype=jnp.int32),
            (args.batch, args.prompt_len))
        batch["positions"] = jnp.stack([pos, pos, pos])
    if cfg.family == "audio":
        batch["enc_embeds"] = jnp.asarray(np.random.default_rng(
            args.seed).standard_normal(
            (args.batch, steps_mod.enc_len_for(cfg, args.prompt_len),
             cfg.d_model)).astype(np.float32))

    def make_cache():
        if cfg.family == "audio":
            cache = mod.init_cache(
                cfg, args.batch, total,
                steps_mod.enc_len_for(cfg, args.prompt_len))
        else:
            cache = mod.init_cache(cfg, args.batch, total)
        return jax.device_put(
            cache, shard_rules.cache_sharding(cache, mesh))

    with jax.set_mesh(mesh):
        params = mod.init(cfg, jax.random.PRNGKey(args.seed))
        params = jax.device_put(
            params, shard_rules.param_sharding(params, mesh))

        prefill = jax.jit(steps_mod.make_prefill_step(cfg),
                          donate_argnums=(2,))
        decode = jax.jit(steps_mod.make_decode_step(cfg),
                         donate_argnums=(2,))
        sample = jax.jit(sampler)
        key = jax.random.PRNGKey(args.seed)

        def generate(cache, key):
            t0 = time.monotonic()
            logits, cache = prefill(params, batch, cache)
            logits.block_until_ready()
            t_prefill = time.monotonic() - t0
            key, sub = jax.random.split(key)
            tok = sample(logits, sub)[:, None]
            out_tokens = [tok]
            t1 = time.monotonic()
            for _ in range(args.gen - 1):
                logits, cache = decode(params, tok, cache)
                key, sub = jax.random.split(key)
                tok = sample(logits, sub)[:, None]
                out_tokens.append(tok)
            tok.block_until_ready()
            t_decode = time.monotonic() - t1
            return out_tokens, t_prefill, t_decode

        t_warm0 = time.monotonic()
        if args.warmup:
            # compile prefill+decode+sample off the clock; the timed run
            # below then measures steady-state execution only
            generate(make_cache(), key)
        t_warmup = time.monotonic() - t_warm0

        out_tokens, t_prefill, t_decode = generate(make_cache(), key)

    gen = np.concatenate([np.asarray(t) for t in out_tokens], axis=1)
    summary = {
        "schema": 1,
        "kind": "serve_summary",
        "arch": cfg.name,
        "mode": "static",
        "sampling": sampling_args(args)["method"],
        "batch": args.batch,
        "prompt_len": args.prompt_len,
        "generated": args.gen,
        "warmup_s": t_warmup,
        "prefill_s": t_prefill,
        "decode_s": t_decode,
        "decode_tok_per_s": args.batch * (args.gen - 1) /
        max(t_decode, 1e-9),
        "sample_tokens": gen[0, :8].tolist(),
    }
    return summary, gen


def main(argv=None):
    compile_cache.enable()
    args = build_parser().parse_args(argv)
    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    lm.check_serving(cfg)
    mesh = make_dev_mesh(args.model_parallel)
    obs = obs_mod.from_args(args)
    # vlm/audio prompts need modality inputs the engine doesn't take
    # yet — those archs keep serving on the fixed-batch path
    if args.static or cfg.family in ("vlm", "audio"):
        with obs.span("serve_static"):
            summary, out = serve_static(cfg, args, mesh)
    else:
        summary, out = serve_engine(cfg, args, mesh, obs=obs)
    if obs.enabled:
        # both engines' end-of-run summaries go through the same
        # exporters: a schema-stable JSONL record + the metric snapshot
        paths = obs.flush(summary=summary)
        print(obs.console("serve summary"))
        if paths:
            print(json.dumps({"obs_artifacts": paths}, indent=1))
        obs.close()
    print(json.dumps(summary, indent=1))
    return summary, out


if __name__ == "__main__":
    main()
