"""One cell of the benchmark: set-up, measured window, optional trace, and
the comparison with the plain reference.

A cell is ``<config>.<traffic>``: ``configs/<config>.json`` holds the
model's published sizes (and the arguments the program is built with),
``traffic/<traffic>.json`` the batch, the K-FAC cadence and the optimizer
settings, ``limits/<cell>.json`` the limits of the comparison, and
``metrics/<metric>.py`` one reader per metric. Nothing here names a cell.

Set-up builds the trainer's K-FAC program (``KFACProgram.make_step``, the
step function the training loop calls), makes the weights on the device
from the seed, and drives the first steps through that step function with
the batches the window uses, which compiles every program the window
runs. Those steps are the ones the reference follows. The window then
runs whole cadence periods until ``seconds`` have passed, each batch
placed while the step before it runs, and closes when the device is done.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import glob
import importlib.util
import json
import math
import os
import time
from typing import Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import check  # noqa: E402
from synthetic import SyntheticTokens  # noqa: E402

#: steps the reference follows
CHECK_STEPS = 3


def _load_json(*parts) -> dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_module(path: str):
    name = "bench_" + os.path.basename(path)[:-3].replace("-", "_") \
        .replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def arch(self) -> dict:
        """The reference's sizes, from the published keys."""
        c = self.config
        return {
            "n_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
            "n_heads": c["num_attention_heads"],
            "n_kv_heads": c["num_key_value_heads"],
            "head_dim": c["hidden_size"] // c["num_attention_heads"],
            "d_ff": c["intermediate_size"], "vocab": c["vocab_size"],
            "rope_theta": float(c["rope_theta"]),
            "norm_eps": float(c["rms_norm_eps"]),
        }

    @property
    def reference(self):
        """The configuration's plain reference module."""
        return load_module(os.path.join(BENCH, "reference",
                                        self.config["reference"] + ".py"))

    @property
    def period(self) -> int:
        t = self.traffic
        return math.lcm(t["stats_every"], t["inv_every"])


def load_cell(name: str, bench_json: Optional[dict] = None,
              chips: Optional[int] = None) -> Cell:
    """The cell ``name`` of BENCHMARK.json; with ``chips``, any
    ``<config>.<traffic>`` pair of files, listed or not."""
    if bench_json is None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            bench_json = json.load(f)
    cells = {w["name"]: w for w in bench_json["workloads"]}
    if name in cells:
        w = cells[name]
    elif chips is not None:
        config = max((c[:-5] for c in os.listdir(os.path.join(BENCH,
                                                              "configs"))
                      if name.startswith(c[:-5] + ".")), key=len)
        w = {"config": config, "traffic": name[len(config) + 1:],
             "chips": chips}
    else:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    limits = os.path.join(BENCH, "limits", name + ".json")

    def applies(m):
        return name in m.get("workloads", [name])

    return Cell(
        name=name, chips=w["chips"],
        config=_load_json("configs", w["config"] + ".json"),
        traffic=_load_json("traffic", w["traffic"] + ".json"),
        limits=_load_json(limits)["limits"] if os.path.exists(limits)
        else {},
        end_to_end=[m for m in bench_json["end_to_end"] if applies(m)],
        per_layer=[m for m in bench_json["per_layer"] if applies(m)])


class HalfBatch:
    """A fault: the second half of every batch repeats the first, so the
    step's mean runs over half of the rows."""

    def __init__(self, ds):
        self.ds, self.vocab = ds, ds.vocab
        self.seq_len, self.global_batch = ds.seq_len, ds.global_batch

    def batch_slice(self, step, lo, hi):
        full = self.ds.batch(step)
        half = self.global_batch // 2
        return full[[i % half for i in range(lo, hi)]]


@contextlib.contextmanager
def owner_exchange_skipped():
    """A fault: every tiled all-gather hands back the caller's own shard
    in each slot, so no device sees what another device owns."""
    real = jax.lax.all_gather

    def own_only(x, axis_name, *, axis=0, tiled=False, **_):
        names = axis_name if isinstance(axis_name, tuple) else (axis_name,)
        copies = [x] * math.prod(jax.lax.axis_size(a) for a in names)
        return (jnp.concatenate if tiled else jnp.stack)(copies, axis)

    jax.lax.all_gather = own_only
    try:
        yield
    finally:
        jax.lax.all_gather = real


def span(name):
    return jax.profiler.TraceAnnotation(name)


class Program:
    """The trainer's K-FAC program for one cell, built once; ``start``
    gives it fresh weights for a seed."""

    def __init__(self, cell: Cell, devices, *, obs=None,
                 faults: tuple = ()):
        from repro.configs.base import ModelConfig
        from repro.core import kfac
        from repro.core.kfac import KFACConfig
        from repro.dist import sharding as shard_rules
        from repro.launch import steps as steps_mod
        from repro.launch.train import KFACProgram
        from repro.runtime.elastic import elastic_mesh

        t = cell.traffic
        self.cell, self.faults = cell, faults
        pcfg = dict(cell.config["program"])
        # the model's in-scan Grams and the factor state block alike, as
        # the trainer's --block-size sets both
        pcfg["soi_block"] = min(t["block_size"],
                                pcfg.get("soi_block", t["block_size"]))
        self.cfg = cfg = ModelConfig(**pcfg)
        hp = t["kfac"]
        self.kcfg = kcfg = KFACConfig(
            block_size=cfg.soi_block, stats_every=t["stats_every"],
            inv_every=t["inv_every"], stats_batch=t["batch"],
            stats_seq=t["seq"], precision=t["precision"], **hp)
        self.mesh = mesh = elastic_mesh(t["model_parallel"],
                                        devices=devices)
        # the mesh is set while the program is built, started or run, and
        # not while the reference runs beside it
        scope = self.scope()
        scope.__enter__()
        program = KFACProgram(cfg, kcfg, dist_inv=t["dist_inv"],
                              **({"obs": obs} if obs is not None else {}))
        # the programs trace at their first call: a planted exchange
        # fault stays in place for the program's life
        self._fault_cm = owner_exchange_skipped() if "exchange" in faults \
            else contextlib.nullcontext()
        self._fault_cm.__enter__()
        self.step_fn = program.make_step(mesh)
        ab = steps_mod.abstract_train_state(cfg, kcfg)
        shard = steps_mod.TrainState(
            shard_rules.param_sharding(ab.params, mesh),
            shard_rules.kfac_sharding(ab.kfac, ab.params, mesh))
        mod, specs = steps_mod.model_module(cfg), steps_mod.kfac_specs(cfg)

        def make(key):
            params = mod.init(cfg, key)
            return steps_mod.TrainState(params,
                                        kfac.init(params, specs, kcfg))

        self._init = jax.jit(make, out_shardings=shard)
        self.factored = tuple(sorted(specs))
        self.b1 = kcfg.adam_b1
        arch, ref = cell.arch, cell.reference
        self.key_for = ref.key_for

        @functools.partial(jax.jit, static_argnums=0)
        def leaf_change(leaf, key, p):
            # one leaf of the starting weights at a time: set-up must not
            # hold a second copy of the model beside the program's state
            return check.norm(p - check.leaves_by_path(
                ref.init(arch, key))[leaf])

        self._leaf_change = leaf_change
        scope.__exit__(None, None, None)

    def scope(self):
        return jax.set_mesh(self.mesh)

    def close(self):
        self._fault_cm.__exit__(None, None, None)

    def dataset(self, seed: int):
        t = self.cell.traffic
        return SyntheticTokens(vocab=self.cfg.vocab, seq_len=t["seq"],
                               global_batch=t["batch"], seed=seed)

    def start(self, seed: int):
        with self.scope():
            return self._start(seed)

    def _start(self, seed: int):
        """Weights and state from the seed; the first ``CHECK_STEPS``
        steps through the step function. Returns (state, feed, numbers,
        next batch, inverses): the numbers still on the device, the
        inverses after step 1 on the host. ``check_s`` is the time the
        inverses took to reach the host."""
        from repro.data import DataCursor, make_global_batch

        ds = self.dataset(seed)
        src = HalfBatch(ds) if "half_batch" in self.faults else ds
        mesh = self.mesh

        def feed(i):
            with span("bench:batch"):
                return make_global_batch(src, DataCursor(i), mesh)

        key = self.key_for(seed)
        state = self._init(key)
        if "unchanged" in self.faults:
            real = self.step_fn

            def frozen(st, b):
                _, m = real(jax.tree.map(jnp.copy, st), b)
                return st, m

            step_fn = frozen
        else:
            step_fn = self.step_fn
        batch = feed(0)
        losses, stats_losses, first = {}, {}, None
        for i in range(CHECK_STEPS):
            with span("bench:step"):
                state, m = step_fn(state, batch)
            batch = feed(i + 1)
            losses[i] = m["loss"]
            if "stats_loss" in m:
                stats_losses[i] = m["stats_loss"]
            if i == 0:
                k = state.kfac
                first = check.first_norms(k.momentum, k.adam_mu, k.factors,
                                          self.factored, self.b1)
                # the inverses are compared block by block once the
                # reference has run: they wait on the host, not in HBM
                t0 = time.perf_counter()
                inverses = jax.device_get(k.inverses)
                self.check_s = time.perf_counter() - t0
        change = {k: self._leaf_change(k, key, p) for k, p in
                  check.leaves_by_path(state.params).items()}
        numbers = {"losses": losses, "stats_losses": stats_losses,
                   "first": first, "change": change}
        self.step = step_fn
        return state, feed, numbers, batch, inverses


class Reference:
    """The configuration's plain reference over a cell's first steps, its
    programs compiled once for any number of seeds."""

    def __init__(self, cell: Cell, prec: str = "reference"):
        t = cell.traffic
        self.cell = cell
        arch, ref = cell.arch, cell.reference
        self.ref = ref
        self.model = model = ref.Model(arch, t["block_size"], ref.Prec(prec))
        self.opt = opt = ref.KFAC(model, dict(t["kfac"]))
        self.fns = {
            "init": jax.jit(lambda key: ref.init(arch, key)),
            "stats": jax.jit(model.stats),
            "grads": jax.jit(model.grads),
            "ema": jax.jit(opt.update_factors),
            "invert": jax.jit(opt.invert),
            "apply": jax.jit(opt.apply, donate_argnums=(0, 2)),
        }

    def numbers(self, seed: int, inverses=None, keep: bool = False,
                steps: int = CHECK_STEPS) -> dict:
        """The compared numbers over the first ``steps`` steps; with
        ``inverses`` (another run's inverses after step 1) the block gaps
        of those against this run's; with ``keep`` this run's own
        inverses after step 1 on the host, under ``"inverses"``."""
        t, fns, opt = self.cell.traffic, self.fns, self.opt
        ds = SyntheticTokens(vocab=self.cell.arch["vocab"],
                             seq_len=t["seq"], global_batch=t["batch"],
                             seed=seed)
        kept = None
        with jax.default_matmul_precision("highest"):
            key = self.ref.key_for(seed)
            params = fns["init"](key)
            st = opt.init_state(params)
            st["step"] = jnp.zeros((), jnp.int32)
            out = {"losses": {}, "stats_losses": {}}
            for i in range(steps):
                tokens = jnp.asarray(ds.batch(i))
                if i % t["stats_every"] == 0:
                    a, g, sl = fns["stats"](params, tokens)
                    st["factors"] = fns["ema"](st["factors"], a, g)
                    out["stats_losses"][i] = sl
                    del a, g
                if i % t["inv_every"] == 0:
                    st["inverses"] = fns["invert"](st["factors"])
                loss, grads = fns["grads"](params, tokens)
                out["losses"][i] = loss
                if i == 0:
                    out["grad0"] = check.tree_norms(grads)
                params, st = fns["apply"](params, grads, st)
                del grads
                if i == 0:
                    out["first"] = check.first_norms(
                        st["momentum"], st["adam_mu"], st["factors"],
                        tuple(sorted(opt.factored)), opt.hp["adam_b1"])
                    if inverses is not None:
                        out["inverse"] = check.inverse_gaps(
                            inverses, st["inverses"])
                    if keep:
                        kept = jax.device_get(st["inverses"])
            out["change"] = check.change_norms(params, fns["init"](key))
            out = check.to_host(out)
        if keep:
            out["inverses"] = kept
        return out


@dataclasses.dataclass
class Window:
    steps: int
    tokens: int
    seconds: float
    losses: List[float]
    stats_calls: int
    inv_calls: int
    compiles: int
    #: host seconds of each step call (the step function waits on the
    #: device's step counter, so a stall shows in the step it hit)
    step_walls: List[float] = dataclasses.field(default_factory=list)

    def stalls(self, period: int) -> str:
        """The step furthest over the median of its place in the cadence
        period, for standard error."""
        w = self.step_walls
        if not w:
            return "no steps"
        med = [sorted(w[j::period])[len(w[j::period]) // 2]
               for j in range(min(period, len(w)))]
        over = [x - med[i % period] for i, x in enumerate(w)]
        at = max(range(len(w)), key=over.__getitem__)
        return (f"step walls: {sum(w):.4f} s in {len(w)} steps; step {at} "
                f"took {w[at]:.4f} s, {over[at]:.4f} s over the median of "
                f"its place in the period")


class CompileCounter:
    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if "backend_compile" in event:
            self.n += 1


def run_window(prog: Program, state, feed, batch, seconds: float,
               first_index: int):
    """Whole cadence periods until ``seconds`` have passed."""
    t = prog.cell.traffic
    period = prog.cell.period
    i = first_index
    losses, walls = [], []
    stats_calls = inv_calls = 0
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    with prog.scope(), span("bench:window"):
        while True:
            for _ in range(period):
                stats_calls += i % t["stats_every"] == 0
                inv_calls += i % t["inv_every"] == 0
                ts = time.perf_counter()
                with span("bench:step"):
                    state, m = prog.step(state, batch)
                walls.append(time.perf_counter() - ts)
                losses.append(m["loss"])
                i += 1
                batch = feed(i)
            if time.perf_counter() - t0 >= seconds:
                break
        jax.block_until_ready(state)
    t1 = time.perf_counter()
    n = i - first_index
    w = Window(steps=n, tokens=n * t["batch"] * t["seq"], seconds=t1 - t0,
               losses=[float(x) for x in jax.device_get(losses)],
               stats_calls=stats_calls, inv_calls=inv_calls, compiles=0,
               step_walls=walls)
    return state, w


def peak_bytes(devices) -> int:
    return max(int(d.memory_stats()["peak_bytes_in_use"]) for d in devices)


class Reading:
    """What a metric reader may read (see ``metrics/``)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def make_reading(cell: Cell, chips: int, peaks: dict, window: Window,
                 setup_s: float, peak: int, trace=None) -> Reading:
    t = cell.traffic
    arch, ref = cell.arch, cell.reference
    blocks = ref.factor_blocks(arch, t["block_size"])
    return Reading(
        cell=cell, chips=chips, peaks=peaks, window=window,
        setup_s=setup_s, peak_bytes=peak,
        flops_per_token=ref.train_flops_per_token(arch, t["seq"]),
        inv_blocks=[(bs, nb * arch["n_layers"])
                    for nb, bs in blocks.values()],
        dist_inv=bool(t["dist_inv"]), trace=trace)


def read_metrics(specs: List[dict], reading: Reading) -> Dict[str, dict]:
    out = {}
    for m in specs:
        mod = load_module(os.path.join(BENCH, "metrics", m["name"] + ".py"))
        v = mod.read(reading)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def trace_dir() -> str:
    return os.path.join(ROOT, "bench_traces")


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices,
        t_start: float, peaks: dict, faults: tuple = (),
        log=print) -> dict:
    """One run of a cell; returns the result line as a dict."""
    from repro import obs as obs_mod

    compiles = CompileCounter()
    obs = obs_mod.Observability(enabled=True, annotate=True) if trace \
        else None
    prog = Program(cell, devices, obs=obs, faults=faults)
    state, feed, prog_nums, batch, inverses = prog.start(seed)
    prog_nums = check.to_host(prog_nums)
    jax.block_until_ready(state)
    # the copy of the inverses for the check is not set-up
    setup_s = time.perf_counter() - t_start - prog.check_s
    n_compiled = compiles.n
    tdir = None
    if trace:
        tdir = os.path.join(trace_dir(), f"{cell.name}.{seed}")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    state, win = run_window(prog, state, feed, batch, seconds, CHECK_STEPS)
    if trace:
        jax.profiler.stop_trace()
    win.compiles = compiles.n - n_compiled
    peak = peak_bytes(devices)
    del state, batch, feed
    prog.close()
    del prog
    gc.collect()

    t_ref = time.perf_counter()
    ref = Reference(cell).numbers(seed, inverses)
    del inverses
    log(f"reference: {time.perf_counter() - t_ref:.1f} s")
    gaps = check.compare(prog_nums, ref)
    failed = sum(not math.isfinite(x) for x in win.losses)
    ok, table = check.verdict(gaps, cell.limits, extra_ok=failed == 0)

    reading = make_reading(cell, len(devices), peaks, win, setup_s, peak)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"correct": bool(ok), "attempted": win.steps,
              "failed": failed}
    if trace:
        import trace_reduce

        path = sorted(glob.glob(os.path.join(
            tdir, "**", "*.xplane.pb"), recursive=True))[-1]
        tr = trace_reduce.reduce(trace_reduce.load(path))
        reading.trace = tr
        result["metrics"] = read_metrics(cell.per_layer, reading)
        device.update(busy_s=tr.busy_s, window_s=tr.window_s)
        result["device"] = device
        result["breakdown"] = {"device_ops": tr.top_ops,
                               "idle_gaps": tr.idle_gaps}
    else:
        result["metrics"] = read_metrics(cell.end_to_end, reading)
        result["device"] = device
    result["check"] = {k: {"value": v["value"], "limit": v["limit"]}
                       for k, v in table.items()}
    log(win.stalls(cell.period))
    for k, v in table.items():
        log(f"check {k}: {v['value']!r} limit {v['limit']!r} "
            f"(worst at {v['at']})")
    log(f"check window: {win.steps} steps, {failed} non-finite losses, "
        f"{win.compiles} compiles")
    return result
