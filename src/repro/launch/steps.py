"""Step builders + abstract input specs for every (arch x shape) cell.

One *cell* = (architecture, input-shape) from the assignment grid. Each
cell lowers one of:

  train_4k      -> ``train_step``  (fwd + bwd + K-FAC precondition +
                   update; the SU/INV graphs lower separately as
                   ``stats_step`` / ``inv_step`` — the paper amortizes
                   them over ``stats_every`` batches, Fig. 8)
  prefill_32k   -> ``prefill_step`` (prompt pass writing the KV cache)
  decode_32k,
  long_500k     -> ``decode_step``  (one token against a seq_len cache)

Everything here is ShapeDtypeStruct-abstract: no allocation. The same
builders are jitted concretely by launch/train.py / launch/serve.py and
the smoke tests (reduced configs).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeCfg
from repro.core import kfac
from repro.core.kfac import KFACConfig, KFACState
from repro.dist import sharding as shard_rules
from repro.dist.api import (
    BATCH_AXES,
    mesh_ndev,
    shard_hint,
    shard_like_params,
)
from repro.models import lm, whisper
from repro.solve import invert_factor_tree, make_plan, make_wu_plan


class TrainState(NamedTuple):
    params: Any
    kfac: KFACState


def model_module(cfg: ModelConfig):
    return whisper if cfg.family == "audio" else lm


def kfac_specs(cfg: ModelConfig):
    return model_module(cfg).kfac_specs(cfg)


def enc_len_for(cfg: ModelConfig, seq: int) -> int:
    """Whisper frame count for a given assigned seq_len (the real model
    uses 1500 frames; we honor the assigned seq on the decoder side)."""
    return min(1500, seq)


# ---------------------------------------------------------------------------
# Abstract state
# ---------------------------------------------------------------------------

def abstract_params(cfg: ModelConfig):
    mod = model_module(cfg)
    return jax.eval_shape(lambda: mod.init(cfg, jax.random.PRNGKey(0)))


def abstract_train_state(cfg: ModelConfig, kcfg: KFACConfig) -> TrainState:
    params = abstract_params(cfg)
    specs = kfac_specs(cfg)
    kstate = jax.eval_shape(lambda: kfac.init(params, specs, kcfg))
    return TrainState(params, kstate)


def abstract_serve_params(cfg: ModelConfig):
    """Serving stores weights bf16 (compute dtype); fp32 master weights
    are a training-only concern."""
    params = abstract_params(cfg)
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, jnp.bfloat16 if s.dtype == jnp.float32 else s.dtype),
        params)


def abstract_cache(cfg: ModelConfig, batch: int, seq_len: int):
    mod = model_module(cfg)
    if cfg.family == "audio":
        return jax.eval_shape(lambda: mod.init_cache(
            cfg, batch, seq_len, enc_len_for(cfg, seq_len)))
    return jax.eval_shape(lambda: mod.init_cache(cfg, batch, seq_len))


# ---------------------------------------------------------------------------
# Abstract batches
# ---------------------------------------------------------------------------

def train_batch_sds(cfg: ModelConfig, batch: int, seq: int) -> Dict:
    sds = {"tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32)}
    if cfg.family == "vlm":
        sds["img_embeds"] = jax.ShapeDtypeStruct(
            (batch, cfg.n_img_tokens, cfg.vision_dim), jnp.float32)
        sds["positions"] = jax.ShapeDtypeStruct(
            (3, batch, seq), jnp.int32)
    if cfg.family == "audio":
        sds["enc_embeds"] = jax.ShapeDtypeStruct(
            (batch, enc_len_for(cfg, seq), cfg.d_model), jnp.float32)
    return sds


def stats_batch_shape(cfg: ModelConfig, shape: ShapeCfg,
                      kcfg: KFACConfig) -> Tuple[int, int]:
    """SU-graph subsample (paper: SOI updated every 10 batches on one
    batch; we additionally subsample tokens to bound tap memory)."""
    b = min(shape.global_batch, kcfg.stats_batch)
    s = min(shape.seq_len, kcfg.stats_seq)
    return b, s


# ---------------------------------------------------------------------------
# Step functions
# ---------------------------------------------------------------------------

def _split_microbatches(batch, accum: int):
    """Reshape every batch leaf to a leading (accum, mb, ...) layout.

    The split itself lives in ``repro.pipeline.microbatch`` (shared
    with the pipeline executor, which feeds the same microbatches
    through its schedule); this wrapper adds the layout hints: the
    microbatch dim keeps the (pod, data) sharding (the reshape is
    local because accum divides the per-shard row count)."""
    from repro.pipeline.microbatch import split_microbatches

    out = {}
    for k, v in split_microbatches(batch, accum).items():
        if k == "positions" and v.ndim >= 4:
            out[k] = shard_hint(v, None, None, BATCH_AXES)
        else:
            out[k] = shard_hint(v, None, BATCH_AXES)
    return out


def make_wu_plan_for(cfg: ModelConfig, kcfg: KFACConfig, *,
                     ndev: int = 1,
                     abstract_state: Optional[TrainState] = None):
    """Pooled WU plan for this (arch, kcfg) from abstract factor shapes
    (no allocation). The same plan object feeds ``make_train_step`` and
    the distributed fused-WU solver (``repro.solve.fused_wu``)."""
    ab = abstract_state or abstract_train_state(cfg, kcfg)
    return make_wu_plan(kfac_specs(cfg), ab.kfac.factors, kcfg,
                        ndev=ndev)


def make_train_step(cfg: ModelConfig, kcfg: KFACConfig,
                    wu_plan=None) -> Callable:
    """One FP+BP+WU step. ``wu_plan`` (``repro.solve.WUPlan``) routes
    the WU graph through the pooled fused program — one batched
    VMM⊕INV per (bi, bo) pool plus fused elementwise chains — instead
    of the per-leaf loop; outputs are bitwise identical."""
    mod = model_module(cfg)
    specs = kfac_specs(cfg)
    accum = max(cfg.train_accum, 1)

    def grads_of(params, batch):
        def loss_of(p):
            loss, _ = mod.loss_fn(cfg, p, batch)
            return loss

        loss, grads = jax.value_and_grad(loss_of)(params)
        # keep stacked dW sharded like the params (dist.api docstring)
        return loss, shard_like_params(grads)

    def train_step(state: TrainState, batch) -> Tuple[TrainState, dict]:
        if accum == 1:
            loss, grads = grads_of(state.params, batch)
        else:
            micro = _split_microbatches(batch, accum)
            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)

            def body(carry, mb):
                g_acc, l_acc = carry
                loss, grads = grads_of(state.params, mb)
                g_acc = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32) / accum,
                    g_acc, grads)
                return (g_acc, l_acc + loss / accum), None

            (grads, loss), _ = jax.lax.scan(
                body, (zeros, jnp.zeros((), jnp.float32)), micro)
        return _wu_tail(state, loss, grads, specs, kcfg, wu_plan)

    return train_step


def _wu_tail(state: TrainState, loss, grads, specs, kcfg: KFACConfig,
             wu_plan) -> Tuple[TrainState, dict]:
    """The WU graph + metrics shared by the monolithic and pipelined
    steps: K-FAC precondition + update on the accumulated gradients,
    grad-norm metric — one definition, so both paths always report and
    update identically."""
    params2, kstate2 = kfac.apply_updates(
        state.params, grads, state.kfac, specs, kcfg, wu_plan=wu_plan)
    gnorm = jnp.sqrt(sum(
        jnp.sum(jnp.square(g.astype(jnp.float32)))
        for g in jax.tree.leaves(grads)))
    return (TrainState(params2, kstate2),
            {"loss": loss, "grad_norm": gnorm})


def make_pipeline_step(cfg: ModelConfig, kcfg: KFACConfig, *,
                       mesh=None, pp: int = 1, schedule="1f1b",
                       n_micro: Optional[int] = None,
                       wu_plan=None) -> Callable:
    """Pipeline-parallel FP+BP+WU step over the ``stage`` mesh axis.

    The layer stack is cut into ``pp`` contiguous stages
    (``pipeline.partition_stages``), the batch into microbatches
    (``n_micro``, default ``max(train_accum, pp)``), and the
    ``schedule`` — "gpipe" | "1f1b", or an already-built
    ``pipeline.Schedule`` (so callers that also need the schedule for
    bubble accounting build it exactly once) — is lowered into one
    shard_map program with ppermute transfers
    (``pipeline.make_pipeline_grads_fn``). Loss/gradients keep the
    gradient-accumulation semantics, and the WU tail (K-FAC
    precondition + update, optionally pooled via ``wu_plan``) is the
    same ``_wu_tail`` the monolithic step runs.

    ``pp=1`` returns :func:`make_train_step` itself — the monolithic
    program, bitwise-identical to today's path by construction.
    """
    if pp <= 1:
        return make_train_step(cfg, kcfg, wu_plan=wu_plan)
    from repro import pipeline

    lm.check_pipeline(cfg)
    if mesh is None:
        raise ValueError("pp > 1 needs a mesh with a 'stage' axis "
                         "(launch.mesh.make_pipeline_mesh)")
    # free (cost-balanced) partition: the executor handles non-uniform
    # atom counts via static padding + masks; uniform counts keep the
    # unpadded bitwise path automatically
    part = pipeline.partition_stages(cfg, pp)
    m = n_micro or max(cfg.train_accum, pp)
    if isinstance(schedule, pipeline.Schedule):
        sched = schedule
        if (sched.n_stages, sched.n_micro) != (pp, m):
            raise ValueError(
                f"schedule was built for (S={sched.n_stages}, "
                f"M={sched.n_micro}), step wants (S={pp}, M={m})")
    else:
        sched = pipeline.make_schedule(schedule, pp, m)
    grads_fn = pipeline.make_pipeline_grads_fn(cfg, part, sched, mesh)
    specs = kfac_specs(cfg)

    data_shards = 1
    for ax in ("pod", "data"):
        data_shards *= dict(mesh.shape).get(ax, 1)

    def train_step(state: TrainState, batch) -> Tuple[TrainState, dict]:
        b = batch["tokens"].shape[0]
        if b % (m * data_shards):
            raise ValueError(
                f"global batch {b} must divide into n_micro={m} "
                f"microbatches x {data_shards} data shard(s); pick a "
                f"batch that is a multiple of {m * data_shards}")
        micro = pipeline.split_microbatches(batch, m)
        loss, grads = grads_fn(state.params, micro)
        grads = shard_like_params(grads)
        return _wu_tail(state, loss, grads, specs, kcfg, wu_plan)

    return train_step


def make_sgd_step(cfg: ModelConfig, lr: float = 1e-2,
                  momentum: float = 0.9) -> Callable:
    """First-order baseline (the paper's GPU-1st / PipeLayer side)."""
    mod = model_module(cfg)

    def sgd_step(state, batch):
        params, mom = state

        def loss_of(p):
            loss, _ = mod.loss_fn(cfg, p, batch)
            return loss

        loss, grads = jax.value_and_grad(loss_of)(params)
        grads = shard_like_params(grads)
        mom2 = jax.tree.map(lambda m, g: momentum * m + g, mom, grads)
        params2 = jax.tree.map(lambda p, m: p - lr * m, params, mom2)
        return (params2, mom2), {"loss": loss}

    return sgd_step


def _build_taps(cfg: ModelConfig, mod, specs, batch):
    """Zero tap buffers for one stats batch (audio keeps per-name token
    counts: encoder taps see frames, decoder taps see tokens)."""
    b, t = batch["tokens"].shape
    if cfg.family == "audio":
        te = batch["enc_embeds"].shape[1]
        taps = {}
        for name, s in specs.items():
            n_tok = b * (te if name.startswith("enc/") else t)
            taps[name] = jnp.zeros(
                s.stack + (n_tok, s.d_out), jnp.float32)
        return taps
    return mod.build_taps(cfg, specs, b * t)


def make_stats_step(cfg: ModelConfig, kcfg: KFACConfig) -> Callable:
    """SU graph: factor Grams on a token subsample, EMA'd into state."""
    mod = model_module(cfg)
    specs = kfac_specs(cfg)

    def stats_step(state: TrainState, batch) -> Tuple[TrainState, dict]:
        taps = _build_taps(cfg, mod, specs, batch)

        def loss_with_taps(p, tp, bt):
            return mod.loss_fn(cfg, p, bt, taps=tp, collect=True)

        a_grams, g_grams, loss, extras = kfac.stats_grams(
            loss_with_taps, state.params, taps, batch, specs,
            kcfg.block_size)
        kstate2 = kfac.update_factors(state.kfac, a_grams, g_grams, kcfg)
        metrics = {"stats_loss": loss}
        metrics.update(moe_counters(extras))
        return state._replace(kfac=kstate2), metrics

    return stats_step


def moe_counters(extras: dict) -> dict:
    """Counters of the expert layer on a statistics pass, from its held
    experts' token counts (``<stack>/moe/counts``, (L, held)):
    ``moe_held_tokens``, the (token, choice) pairs routed to the held
    experts per layer, averaged over the layers; and
    ``moe_busiest_over_mean``, the busiest held expert's count over the
    held experts' mean, the largest over the layers. Empty for models
    without the layer."""
    counts = [v.astype(jnp.float32) for k, v in sorted(extras.items())
              if k.endswith("/moe/counts")]
    if not counts:
        return {}
    c = jnp.concatenate(counts)
    per_layer = jnp.sum(c, axis=-1)
    busiest = jnp.max(c, axis=-1) / jnp.maximum(
        jnp.mean(c, axis=-1), 1e-9)
    return {"moe_held_tokens": jnp.mean(per_layer),
            "moe_busiest_over_mean": jnp.max(busiest)}


def make_smw_step(cfg: ModelConfig, kcfg: KFACConfig,
                  scfg=None) -> Callable:
    """Fused SU + incremental-INV graph: rank-k stats, factor EMA, SMW
    inverse update and the drift probe in ONE program.

    The same tap construction as :func:`make_stats_step`, but the model
    collects column factors (``collect="cols"``) so the Gram never has
    to be re-factored; ``kfac.stats_rank_k`` keeps the factor-EMA
    trajectory bitwise identical to the ``stats_grams`` path while also
    exposing the columns the Woodbury update consumes. Runs every step
    (SMW mode has no stats/inv cadence); the returned metrics carry
    ``smw_drift`` for the host-side fallback gate
    (``repro.solve.SMWRefresher``).
    """
    from repro.solve import smw as smw_mod

    scfg = scfg or smw_mod.SMWConfig()
    mod = model_module(cfg)
    specs = kfac_specs(cfg)

    def smw_step(state: TrainState, batch) -> Tuple[TrainState, dict]:
        taps = _build_taps(cfg, mod, specs, batch)

        def loss_with_taps(p, tp, bt):
            return mod.loss_fn(cfg, p, bt, taps=tp, collect="cols")

        a_grams, g_grams, cols, loss = kfac.stats_rank_k(
            loss_with_taps, state.params, taps, batch, specs,
            kcfg.block_size)
        kstate2 = kfac.update_factors(state.kfac, a_grams, g_grams, kcfg)
        new_inv, drift = smw_mod.smw_refresh(
            kstate2.inverses, kstate2.factors, cols, kcfg, scfg)
        kstate2 = kstate2._replace(inverses=new_inv)
        return (state._replace(kfac=kstate2),
                {"stats_loss": loss, "smw_drift": drift})

    return smw_step


def make_inv_refresh(cfg: ModelConfig, kcfg: KFACConfig, *,
                     mesh=None, distributed: bool = False,
                     abstract_state: Optional[TrainState] = None,
                     pdiv_cap_bs: Optional[int] = None) -> Callable:
    """Inverse-refresh fn ``factors -> inverses`` for this (arch, kcfg).

    ``distributed=True`` on a multi-device mesh routes through the
    block-parallel solver (``repro.solve``): a FLOP-cost plan is built
    once from the abstract factor shapes, and each device inverts only
    its owned ~1/ndev of the blocks under shard_map. Otherwise the
    replicated path runs (bitwise-identical per block on the default
    composed method). ``pdiv_cap_bs`` (distributed only) diverts factor
    leaves whose block size exceeds the cap into the plan's pdiv
    sub-schedule — each oversized block is inverted by recursive
    block-Schur (``solve.pdiv_invert``) with its stage pairs spread
    over the mesh instead of serializing one device.

    Operating on the factor subtree (not the whole TrainState) is what
    lets the async refresher dispatch it as an independent computation
    overlapping the train steps. Pass ``abstract_state`` when the
    caller already holds one (whole-model ``eval_shape`` is not free).
    """
    plan = None
    if distributed and mesh is not None and mesh_ndev(mesh) > 1:
        ab = abstract_state or abstract_train_state(cfg, kcfg)
        plan = make_plan(ab.kfac.factors, mesh_ndev(mesh), kcfg,
                         pdiv_cap_bs=pdiv_cap_bs)

    @jax.named_scope("inv")
    def refresh(factors):
        return invert_factor_tree(factors, kcfg, mesh=mesh, plan=plan)

    return refresh


def make_inv_step(cfg: ModelConfig, kcfg: KFACConfig, *,
                  mesh=None, distributed: bool = False) -> Callable:
    """The paper's technique: composed-precision INV of every SOI block."""
    refresh = make_inv_refresh(cfg, kcfg, mesh=mesh,
                               distributed=distributed)

    def inv_step(state: TrainState) -> TrainState:
        kstate = state.kfac
        return state._replace(
            kfac=kstate._replace(inverses=refresh(kstate.factors)))

    return inv_step


def make_prefill_step(cfg: ModelConfig) -> Callable:
    mod = model_module(cfg)

    def prefill_step(params, batch, cache):
        return mod.prefill(cfg, params, batch, cache)

    return prefill_step


def make_decode_step(cfg: ModelConfig) -> Callable:
    mod = model_module(cfg)

    def decode_step(params, token, cache):
        return mod.decode_step(cfg, params, token, cache)

    return decode_step


# ---------------------------------------------------------------------------
# Cell assembly (what dryrun lowers)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Lowerable:
    """One jit-able program with its abstract args and shardings."""

    name: str
    fn: Callable
    args: Tuple[Any, ...]            # ShapeDtypeStruct pytrees
    in_shardings: Tuple[Any, ...]
    donate_argnums: Tuple[int, ...] = ()

    def lower(self):
        jitted = jax.jit(self.fn, in_shardings=self.in_shardings,
                         donate_argnums=self.donate_argnums)
        return jitted.lower(*self.args)


def cell_skip_reason(cfg: ModelConfig, shape: ShapeCfg) -> Optional[str]:
    if shape.name == "long_500k" and not cfg.subquadratic:
        return ("full-attention arch: 524k decode is out of contract "
                "(sub-quadratic archs only; DESIGN.md §4)")
    if shape.kind == "decode" and not cfg.has_decoder:
        return "encoder-only arch has no decode step"
    return None


def build_cell(cfg: ModelConfig, shape: ShapeCfg, mesh,
               kcfg: Optional[KFACConfig] = None,
               *, include_soi: bool = False) -> list:
    """Lowerables for one (arch x shape) cell on ``mesh``."""
    kcfg = kcfg or KFACConfig()
    out = []
    if shape.kind == "train":
        state = abstract_train_state(cfg, kcfg)
        st_shard = TrainState(
            shard_rules.param_sharding(state.params, mesh),
            shard_rules.kfac_sharding(state.kfac, state.params, mesh))
        batch = train_batch_sds(cfg, shape.global_batch, shape.seq_len)
        b_shard = shard_rules.batch_sharding(batch, mesh)
        out.append(Lowerable(
            "train_step", make_train_step(cfg, kcfg), (state, batch),
            (st_shard, b_shard), donate_argnums=(0,)))
        if include_soi:
            sb, ss = stats_batch_shape(cfg, shape, kcfg)
            sbatch = train_batch_sds(cfg, sb, ss)
            out.append(Lowerable(
                "stats_step", make_stats_step(cfg, kcfg),
                (state, sbatch),
                (st_shard, shard_rules.batch_sharding(sbatch, mesh)),
                donate_argnums=(0,)))
            out.append(Lowerable(
                "inv_step", make_inv_step(cfg, kcfg), (state,),
                (st_shard,), donate_argnums=(0,)))
        return out

    params = abstract_serve_params(cfg)
    p_shard = shard_rules.param_sharding(params, mesh)
    if shape.kind == "prefill":
        cache = abstract_cache(cfg, shape.global_batch, shape.seq_len)
        batch = train_batch_sds(cfg, shape.global_batch, shape.seq_len)
        out.append(Lowerable(
            "prefill_step", make_prefill_step(cfg),
            (params, batch, cache),
            (p_shard, shard_rules.batch_sharding(batch, mesh),
             shard_rules.cache_sharding(cache, mesh)),
            donate_argnums=(2,)))
    else:   # decode: one new token against a seq_len cache
        cache = abstract_cache(cfg, shape.global_batch, shape.seq_len)
        token = jax.ShapeDtypeStruct(
            (shape.global_batch, 1), jnp.int32)
        t_shard = shard_rules.batch_sharding({"t": token}, mesh)["t"]
        out.append(Lowerable(
            "decode_step", make_decode_step(cfg),
            (params, token, cache),
            (p_shard, t_shard, shard_rules.cache_sharding(cache, mesh)),
            donate_argnums=(2,)))
    return out
