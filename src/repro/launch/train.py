"""End-to-end training driver: K-FAC (or SGD baseline) + fault-tolerant
loop + checkpointing + synthetic data, on whatever devices exist.

CPU quickstart (reduced config, real steps):
  JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.train \
      --arch qwen1.5-0.5b --smoke --steps 40 --batch 8 --seq 64 \
      --ckpt-dir /tmp/ck

On a TPU host, drop ``--smoke`` for the published widths (one v5e
holds qwen2-0.5b: ``--batch 8 --seq 1024 --lr 3e-3``; the default
``--lr 3e-2`` overshoots at that width, the loss rising from 12.1 to
17.7 by the third step) and add
``--model-parallel N`` / ``--pp N`` / ``--dist-inv`` on a slice; the
mesh comes from ``runtime.elastic`` so a shrunk device pool after a
failure re-forms automatically (drill it with ``--inject-failure-at
N``). ``--ckpt-dir`` is restored from when it holds a checkpoint.

The K-FAC cadence follows the paper (Fig. 8): FP/BP/WU every step; the
SU graph (factor stats) every ``--stats-every`` steps on a subsampled
batch; the INV graph (composed-precision block inverses — the paper's
technique) every ``--inv-every`` steps.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import logging
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro import obs as obs_mod
from repro.configs import get_config, get_smoke_config
from repro.core import kfac, quantize
from repro.core.kfac import KFACConfig
from repro.data import SyntheticTokens
from repro.dist import sharding as shard_rules
from repro.dist.api import mesh_ndev
from repro.launch import compile_cache
from repro.launch import steps as steps_mod
from repro.launch.steps import TrainState
from repro.runtime import DeviceLoss, LoopConfig, TrainLoop, elastic_mesh
from repro.solve import AsyncInverseRefresher, SMWConfig, SMWRefresher


def _key_of_path(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "|".join(parts)


def _sharding_lookup(tree) -> dict:
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {_key_of_path(p): s for p, s in leaves}


def _phase(obs, name, cat="dispatch"):
    """``phase:<name>`` span, a no-op unless ``obs`` is enabled.
    Dispatch-timed on purpose: fencing each phase would serialize
    exactly the async overlap (inv refresh, pipelined microbatches) the
    phases exist to exploit; the loop's own step fence gives the honest
    total. ``phase:sync`` brackets the host's one wait on the device."""
    if not obs.enabled:
        return contextlib.nullcontext()
    return obs.tracer.span(f"phase:{name}", cat=cat)


@dataclasses.dataclass
class KFACProgram:
    """K-FAC training program.

    ``dist_inv``: route the SOI inverse refresh through the
    block-parallel solver (repro.solve) — each device inverts only its
    plan-owned ~1/ndev of the factor blocks (no-op on 1 device).
    ``async_inv``: staleness-tolerant double-buffered refresh — step N
    preconditions with the inverses computed at step N - inv_every
    while the next refresh overlaps the following train steps.
    ``fused_wu``: pooled fused WU graph (default) — precondition +
    update run as one batched VMM⊕INV program per (bi, bo) block pool
    instead of a per-leaf loop (bitwise identical; ``--no-fused-wu``
    keeps the legacy path for parity checks).
    ``pp``/``pp_schedule``: pipeline-parallel FP/BP over the ``stage``
    mesh axis (repro.pipeline; ``pp=1`` is the monolithic program).
    With ``async_inv`` the SOI refresh is dispatched right before the
    pipeline program so the INV work overlaps the fill/drain bubbles
    (``pipeline.kfac_glue``).
    ``smw``: incremental SOI — the stats/inv cadences are replaced by
    one fused rank-k program per step (SU stats + factor EMA + SMW
    inverse update + drift probe, ``repro.solve.smw``); the inverses
    are never stale, and a measured drift above ``smw_drift_budget``
    triggers a full re-inversion through the same donated refresh
    program. Mutually exclusive with ``async_inv`` (nothing to
    overlap — there is no inv cadence left).
    """

    cfg: Any
    kcfg: KFACConfig
    seed: int = 0
    dist_inv: bool = False
    async_inv: bool = False
    fused_wu: bool = True
    pp: int = 1
    pp_schedule: str = "1f1b"
    smw: bool = False
    smw_drift_budget: float = 0.05
    smw_rank: int = 64
    obs: Any = None

    def __post_init__(self):
        self.programs = {}
        self._refresher = None
        self._smw = None
        self._sched = None
        if self.obs is None:
            self.obs = obs_mod.NULL
        if self.smw and self.async_inv:
            raise ValueError(
                "--smw refreshes the inverses inside every step; there "
                "is no inv cadence left for --async-inv to overlap")

    def _shardings(self, mesh, ab=None):
        ab = ab or steps_mod.abstract_train_state(self.cfg, self.kcfg)
        return TrainState(
            shard_rules.param_sharding(ab.params, mesh),
            shard_rules.kfac_sharding(ab.kfac, ab.params, mesh))

    def init_state(self, mesh):
        mod = steps_mod.model_module(self.cfg)
        specs = steps_mod.kfac_specs(self.cfg)
        st_shard = self._shardings(mesh)

        def make():
            params = mod.init(self.cfg, jax.random.PRNGKey(self.seed))
            return TrainState(params,
                              kfac.init(params, specs, self.kcfg))

        return jax.jit(make, out_shardings=st_shard)()

    def make_step(self, mesh):
        ab = steps_mod.abstract_train_state(self.cfg, self.kcfg)
        st_shard = self._shardings(mesh, ab)
        b_spec = None      # let jit shard the host batch by its sharding
        wu_plan = steps_mod.make_wu_plan_for(
            self.cfg, self.kcfg, ndev=mesh_ndev(mesh),
            abstract_state=ab) if self.fused_wu else None
        if self.pp > 1:
            from repro.pipeline import make_schedule

            n_micro = max(self.cfg.train_accum, self.pp)
            self._sched = make_schedule(self.pp_schedule, self.pp,
                                        n_micro)
            # pass the built Schedule through so the executing program
            # and the bubble metrics describe the same tick grid
            train_fn = steps_mod.make_pipeline_step(
                self.cfg, self.kcfg, mesh=mesh, pp=self.pp,
                schedule=self._sched, n_micro=n_micro,
                wu_plan=wu_plan)
        else:
            self._sched = None
            train_fn = steps_mod.make_train_step(self.cfg, self.kcfg,
                                                 wu_plan=wu_plan)
        train = jax.jit(train_fn,
                        in_shardings=(st_shard, b_spec),
                        out_shardings=(st_shard, None),
                        donate_argnums=(0,))
        stats = jax.jit(steps_mod.make_stats_step(self.cfg, self.kcfg),
                        in_shardings=(st_shard, b_spec),
                        out_shardings=(st_shard, None),
                        donate_argnums=(0,))
        # Inverse refresh operates on the factor subtree only, so the
        # async mode can dispatch it as an independent computation.
        # One jitted program for both modes — donated: the inverse
        # buffers being retired become the output buffers of the refresh
        # that replaces them (the sync path writes in place, the async
        # path double-buffers; backends without donation support fall
        # back to fresh allocations).
        refresh_raw = steps_mod.make_inv_refresh(
            self.cfg, self.kcfg, mesh=mesh, distributed=self.dist_inv,
            abstract_state=ab)
        inv_shard = st_shard.kfac.inverses
        refresh_into = jax.jit(
            lambda factors, retired: refresh_raw(factors),
            donate_argnums=(1,), keep_unused=True,
            out_shardings=inv_shard)
        if self.async_inv:
            # seed the double buffer so the very first dispatch already
            # runs refresh_into: the single refresh program compiles at
            # step 0 inside the watchdog's warmup window (a second
            # program compiling at the *second* trigger would blow the
            # armed step deadline and start a recovery storm)
            spare = jax.jit(
                lambda: jax.tree.map(
                    lambda s: jnp.zeros(s.shape, s.dtype),
                    ab.kfac.inverses),
                out_shardings=inv_shard)()
            self._refresher = AsyncInverseRefresher(
                refresh_into=refresh_into, spare_buffers=spare,
                obs=self.obs)
        else:
            self._refresher = None
        if self.smw:
            scfg = SMWConfig(drift_budget=self.smw_drift_budget,
                             rank=self.smw_rank)
            smw_jit = jax.jit(
                steps_mod.make_smw_step(self.cfg, self.kcfg, scfg),
                in_shardings=(st_shard, b_spec),
                out_shardings=(st_shard, None),
                donate_argnums=(0,))
            self._smw = SMWRefresher(smw_jit, refresh_into,
                                     drift_budget=self.smw_drift_budget,
                                     obs=self.obs)
        else:
            self._smw = None
        #: the jitted programs step_fn dispatches, by name
        self.programs = {"train": train, "stats": stats,
                         "inv": refresh_into}
        refresher = self._refresher
        smw_ref = self._smw
        kcfg = self.kcfg
        sched = self._sched
        obs = self.obs

        def subsample(batch):
            sb = min(batch["tokens"].shape[0], kcfg.stats_batch)
            ss = min(batch["tokens"].shape[1], kcfg.stats_seq)
            out = {"tokens": batch["tokens"][:sb, :ss]}
            for k in ("img_embeds", "enc_embeds"):
                if k in batch:
                    out[k] = batch[k][:sb]
            if "positions" in batch:
                out["positions"] = batch["positions"][:, :sb, :ss]
            return out

        def step_fn(state: TrainState, batch):
            if smw_ref is not None:
                # incremental SOI: one fused rank-k program every step
                # (stats + EMA + SMW inverse update + drift probe), the
                # host gate falls back to refresh_into on drift
                with _phase(obs, "smw"):
                    state, metrics = smw_ref.step(state,
                                                  subsample(batch))
                with _phase(obs, "train"):
                    state, m = train(state, batch)
                metrics.update(m)
                return state, metrics
            with _phase(obs, "sync", cat="sync"):
                i = int(jax.device_get(state.kfac.step))
            metrics = {}
            if i % kcfg.stats_every == 0:
                with _phase(obs, "stats"):
                    state, m = stats(state, subsample(batch))
                metrics.update(m)
            if i % kcfg.inv_every == 0:
                with _phase(obs, "inv"):
                    if refresher is not None and sched is not None:
                        # pipelined: dispatch the refresh just before
                        # the pipeline program so INV overlaps its
                        # bubbles
                        from repro.pipeline import kfac_glue

                        kstate, info = kfac_glue.bubble_refresh(
                            refresher, state.kfac, sched)
                        state = state._replace(kfac=kstate)
                        metrics.update(info)
                    elif refresher is not None:
                        state = state._replace(
                            kfac=refresher.step(state.kfac))
                    else:
                        kst = state.kfac
                        state = state._replace(kfac=kst._replace(
                            inverses=refresh_into(kst.factors,
                                                  kst.inverses)))
            with _phase(obs, "train"):
                state, m = train(state, batch)
            metrics.update(m)
            return state, metrics

        return step_fn

    # -- async-refresh lifecycle hooks (called by runtime.TrainLoop) ----

    def flush_async(self, state):
        """Snapshot view: the state with any in-flight refresh folded
        in, for checkpointing — the live refresher keeps its pending
        swap, so checkpoint cadence never changes the training
        trajectory."""
        if self._refresher is None:
            return state
        return state._replace(kfac=self._refresher.peek(state.kfac))

    def reset_async(self):
        """Drop the in-flight refresh (elastic recovery: the restored
        factors no longer match what was dispatched)."""
        if self._refresher is not None:
            self._refresher.reset()
        if self._smw is not None:
            self._smw.reset()

    def state_sharding(self, mesh):
        lookup = _sharding_lookup(self._shardings(mesh))
        return lambda key: lookup.get(key)


@dataclasses.dataclass
class SGDProgram:
    """First-order baseline (paper's GPU-1st / PipeLayer side)."""

    cfg: Any
    lr: float = 1e-2
    seed: int = 0

    def _shardings(self, mesh):
        ab = steps_mod.abstract_params(self.cfg)
        ps = shard_rules.param_sharding(ab, mesh)
        return (ps, ps)

    def init_state(self, mesh):
        mod = steps_mod.model_module(self.cfg)

        def make():
            params = mod.init(self.cfg, jax.random.PRNGKey(self.seed))
            return (params, jax.tree.map(jnp.zeros_like, params))

        return jax.jit(make, out_shardings=self._shardings(mesh))()

    def make_step(self, mesh):
        st_shard = self._shardings(mesh)
        return jax.jit(steps_mod.make_sgd_step(self.cfg, self.lr),
                       in_shardings=(st_shard, None),
                       out_shardings=(st_shard, None),
                       donate_argnums=(0,))

    def state_sharding(self, mesh):
        lookup = _sharding_lookup(self._shardings(mesh))
        return lambda key: lookup.get(key)


def main(argv=None, devices=None):
    """Parse ``argv``, train, print and return the loop summary.

    ``devices``: the device pool to mesh over (default: every device of
    the process, ``jax.devices()``)."""
    compile_cache.enable()
    logging.basicConfig(level=logging.INFO)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--optimizer", choices=("kfac", "sgd"),
                    default="kfac")
    ap.add_argument("--lr", type=float, default=3e-2)
    ap.add_argument("--damping", type=float, default=0.03)
    ap.add_argument("--stats-every", type=int, default=10)
    ap.add_argument("--inv-every", type=int, default=10)
    ap.add_argument("--block-size", type=int, default=128)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--pp", type=int, default=1,
                    help="pipeline-parallel stages: the layer stack is "
                         "partitioned over a 'stage' mesh axis and "
                         "microbatches stream through a static "
                         "schedule (repro.pipeline); 1 = monolithic")
    ap.add_argument("--pp-schedule", choices=("gpipe", "1f1b"),
                    default="1f1b",
                    help="microbatch schedule: gpipe (fill then "
                         "drain) or 1f1b (same bubble, min stash)")
    ap.add_argument("--dist-inv", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="block-parallel SOI inversion: each device "
                         "inverts only its plan-owned factor blocks")
    ap.add_argument("--async-inv", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="staleness-tolerant double-buffered inverse "
                         "refresh overlapping the train steps")
    ap.add_argument("--fused-wu", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="pooled fused WU graph: one batched VMM⊕INV "
                         "program for precondition+update (bitwise "
                         "identical to the per-leaf path it replaces)")
    ap.add_argument("--smw", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="incremental SOI: rank-k SMW inverse refresh "
                         "every step (no stats/inv cadence, no stale "
                         "inverses), drift-gated full-reinversion "
                         "fallback")
    ap.add_argument("--smw-drift-budget", type=float, default=0.05,
                    help="probe-residual level that triggers the full "
                         "re-inversion fallback on the SMW path")
    ap.add_argument("--smw-rank", type=int, default=64,
                    help="max rank per SMW update; larger token sets "
                         "are strided down to this many columns")
    ap.add_argument("--precision", default="fp32",
                    choices=quantize.PRECISIONS,
                    help="WU-graph matmul precision (repro.lowp): "
                         "fp32 = historical bitwise path; hilo = bf16 "
                         "limb products (MXU operands are bf16); int8 "
                         "= exact bit-sliced integer products (24-bit "
                         "codes in 8-bit hardware slices)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--inject-failure-at", type=int, default=-1,
                    help="fault drill: raise DeviceLoss at this step")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None,
                    help="write metrics history JSON here")
    # observability (repro.obs)
    ap.add_argument("--obs", action="store_true",
                    help="enable the telemetry spine: phase spans, "
                         "step metrics, recovery/straggler events")
    ap.add_argument("--obs-dir", default=None,
                    help="write JSONL events + Prometheus snapshot + "
                         "Chrome trace here (implies --obs)")
    ap.add_argument("--obs-annotate", action="store_true",
                    help="also emit jax.profiler trace annotations "
                         "for spans")
    args = ap.parse_args(argv)

    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    # the model's in-scan activation Grams and the K-FAC factor state
    # must block features alike
    cfg = dataclasses.replace(
        cfg, soi_block=min(args.block_size, cfg.soi_block))
    obs = obs_mod.from_args(args)
    kcfg = KFACConfig(
        lr=args.lr, damping=args.damping,
        stats_every=args.stats_every, inv_every=args.inv_every,
        block_size=cfg.soi_block,
        stats_batch=args.batch, stats_seq=args.seq,
        precision=args.precision)

    if args.optimizer == "kfac":
        program = KFACProgram(cfg, kcfg, seed=args.seed,
                              dist_inv=args.dist_inv,
                              async_inv=args.async_inv,
                              fused_wu=args.fused_wu,
                              pp=args.pp,
                              pp_schedule=args.pp_schedule,
                              smw=args.smw,
                              smw_drift_budget=args.smw_drift_budget,
                              smw_rank=args.smw_rank,
                              obs=obs)
    else:
        if args.pp > 1:
            raise SystemExit("--pp > 1 is a KFACProgram feature; the "
                             "SGD baseline runs monolithic")
        program = SGDProgram(cfg, lr=args.lr, seed=args.seed)

    ds = SyntheticTokens(vocab=cfg.vocab, seq_len=args.seq,
                         global_batch=args.batch, seed=args.seed)

    fired = []

    def inject(step):
        if step == args.inject_failure_at and not fired:
            fired.append(step)
            raise DeviceLoss(0, "injected failure drill")

    mesh_fn = None if devices is None else (
        lambda exclude=0: elastic_mesh(args.model_parallel, pp=args.pp,
                                       devices=devices, exclude=exclude,
                                       obs=obs))
    loop = TrainLoop(
        LoopConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                   ckpt_every=args.ckpt_every,
                   model_parallel=args.model_parallel,
                   pipeline_parallel=args.pp),
        program, ds, mesh_fn=mesh_fn,
        inject=inject if args.inject_failure_at >= 0 else None,
        obs=obs)
    summary = loop.run()
    print(json.dumps({k: v for k, v in summary.items()
                      if k != "history"}, indent=1))
    losses = [h.get("loss") for h in summary["history"]
              if "loss" in h]
    if losses:
        print(f"loss: first={losses[0]:.4f} last={losses[-1]:.4f}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=1)
    if obs.enabled:
        paths = obs.flush(summary={
            "kind": "train_summary",
            **{k: v for k, v in summary.items() if k != "history"}})
        print(obs.console("train summary"))
        if paths:
            print(json.dumps({"obs_artifacts": paths}, indent=1))
        obs.close()
    return summary


if __name__ == "__main__":
    main()
