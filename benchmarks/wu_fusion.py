"""WU-graph fusion: pooled fused vs per-leaf precondition + update.

The paper's mapping scheme fuses the VMM and INV crossbars so SOI
inverses feed the weight-update VMMs directly (Sec. V). The TPU
analogue (``kfac.apply_updates(wu_plan=...)``) pools same-geometry
factored gradients into batched two-sided block VMMs, replacing the
per-leaf Python loop. Per benchmark arch this measures:

  * WU-step wall time (median of 15 blocked runs — jax dispatch is
    async, so the result is blocked before the clock stops),
  * jaxpr equation count and optimized-HLO entry op count (parameters /
    tuples / bitcasts excluded) of the jitted WU program, plus the dot
    count — the fusion's raw op-count win,
  * optimizer-state bytes: per-path moments (momentum on factored
    leaves, Adam mu/nu on first-order leaves) vs the legacy 3x
    full-model layout,

asserting bitwise parity, strictly fewer ``dot`` kernels (the
launched MXU programs — the paper-level VMM⊕INV fusion claim), fewer
optimized-HLO ops, and a wall-time guard (paired-median fused
advantage is 50-350us on ~1.5-2.5ms steps on quiet hardware, inside
shared-runner noise — wall is measured as *interleaved paired*
rounds so load drift biases neither side, the signed median + win
fraction are recorded, and the assert allows 15% of noise while
still catching the rejected designs' 1.4x+ regressions), and
emitting the machine-readable
``BENCH_wu_fusion.json`` that the CI perf trajectory tracks. The
``fused+ew_pool`` variant (concatenated elementwise chains,
``pool_elementwise=True``) is recorded unasserted: it wins only where
kernel-launch count dominates (TPU), and measures slower on CPU
(EXPERIMENTS.md §Perf 4.2).

``--dist`` instead spawns a forced-4-device child comparing the fused
INV→VMM dataflow (``solve.fused_wu`` owner mode: left VMM on the
device that inverted the block, one collective routing intermediates
to the G owners) against gather-then-replicated-VMM — both
bitwise-checked against the legacy path — and skips the local sweep
(the multidevice CI job should not repeat tier-1's measurements).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import time

import numpy as np

from benchmarks.common import print_csv

_HLO_OP = re.compile(r"^\s+(?:ROOT\s+)?%?[\w.\-]+\s*=\s*\S+\s+([\w\-]+)\(")
_HLO_SKIP = {"parameter", "constant", "get-tuple-element", "tuple",
             "bitcast"}

ARCHS = ("qwen1.5-0.5b", "qwen2-0.5b")
EXTRA_ARCHS = ("moonlight-16b-a3b",)    # recorded, not asserted
BLOCK_SIZE = 16
REPS = 51


def _entry_ops(jitted, *args):
    """(real_ops, dots) of the optimized HLO ENTRY computation — the
    executed op sequence, each fusion counted once."""
    text = jitted.lower(*args).compile().as_text()
    in_entry, real, dots = False, 0, 0
    for line in text.splitlines():
        if line.startswith("ENTRY"):
            in_entry = True
            continue
        if in_entry:
            if line.strip() == "}":
                break
            m = _HLO_OP.match(line)
            if m:
                if m.group(1) not in _HLO_SKIP:
                    real += 1
                if m.group(1) == "dot":
                    dots += 1
    return real, dots


def _median_us_interleaved(fns: dict, *args, n=REPS):
    """Median wall per variant with the variants' reps *interleaved*
    (A B C A B C ...), so machine-load drift during the run biases no
    variant — back-to-back blocks made the comparison flaky on shared
    CPU runners. Each call is blocked to completion before the clock
    stops (async dispatch otherwise times the enqueue). Also returns
    the signed per-round ``per_leaf - fused`` paired differences."""
    import jax

    for fn in fns.values():
        jax.block_until_ready(fn(*args))      # compile off the clock
    ts = {tag: [] for tag in fns}
    for _ in range(n):
        for tag, fn in fns.items():
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            ts[tag].append((time.perf_counter() - t0) * 1e6)
    diffs = np.asarray(ts["per_leaf"]) - np.asarray(ts["fused"])
    return ({tag: float(np.median(v)) for tag, v in ts.items()},
            {"paired_diff_med_us": round(float(np.median(diffs)), 1),
             "fused_win_frac": round(float(np.mean(diffs > 0)), 2)})


def _wu_case(arch: str):
    import jax
    import jax.numpy as jnp

    from repro.configs import get_smoke_config
    from repro.core import kfac
    from repro.core.kfac import KFACConfig
    from repro.launch import steps as steps_mod

    cfg = get_smoke_config(arch)
    kcfg = KFACConfig(block_size=BLOCK_SIZE, ns_iters=6,
                      taylor_terms=2, refine_steps=1)
    mod = steps_mod.model_module(cfg)
    specs = steps_mod.kfac_specs(cfg)
    params = mod.init(cfg, jax.random.PRNGKey(0))
    state = kfac.init(params, specs, kcfg)
    r = np.random.default_rng(0)

    def spd(x):
        bs = x.shape[-1]
        a = r.standard_normal(x.shape[:-1] + (2 * bs,)).astype(
            np.float32)
        return jnp.asarray(
            np.einsum("...ij,...kj->...ik", a, a) / (2 * bs))

    state = state._replace(factors=jax.tree.map(spd, state.factors))
    state = jax.jit(lambda s: kfac.refresh_inverses(s, kcfg))(state)
    grads = jax.tree.map(
        lambda p: jnp.asarray(
            r.standard_normal(p.shape).astype(np.float32)), params)
    wu_plan = steps_mod.make_wu_plan_for(cfg, kcfg)

    variants = {
        "per_leaf": lambda p, g, s: kfac.apply_updates(
            p, g, s, specs, kcfg),
        "fused": lambda p, g, s: kfac.apply_updates(
            p, g, s, specs, kcfg, wu_plan=wu_plan),
        "fused+ew_pool": lambda p, g, s: kfac.apply_updates(
            p, g, s, specs, kcfg, wu_plan=wu_plan,
            pool_elementwise=True),
    }
    jitted = {tag: jax.jit(fn) for tag, fn in variants.items()}
    walls, paired = _median_us_interleaved(jitted, params, grads, state)
    out, params_out = {}, {}
    for tag, fn in variants.items():
        params_out[tag] = jitted[tag](params, grads, state)[0]
        real, dots = _entry_ops(jitted[tag], params, grads, state)
        out[tag] = {
            "wall_ms": round(walls[tag] / 1e3, 3),
            "jaxpr_eqns": len(jax.make_jaxpr(fn)(
                params, grads, state).jaxpr.eqns),
            "hlo_ops": real,
            "hlo_dots": dots,
        }

    ref = jax.tree.leaves(params_out["per_leaf"])
    bitwise = {tag: all(
        bool((np.asarray(a) == np.asarray(b)).all())
        for a, b in zip(ref, jax.tree.leaves(params_out[tag])))
        for tag in ("fused", "fused+ew_pool")}

    p_bytes = sum(np.asarray(x).nbytes for x in jax.tree.leaves(params))
    moment_bytes = sum(
        np.asarray(x).nbytes
        for t in (state.momentum, state.adam_mu, state.adam_nu)
        for x in jax.tree.leaves(t))
    return {
        "arch": arch,
        "block_size": BLOCK_SIZE,
        "n_tiles": wu_plan.total_tiles,
        "stacked_groups": wu_plan.summary()["stacked"],
        "bitwise_equal": bitwise,
        "paired": paired,
        "variants": out,
        "moment_bytes": moment_bytes,
        "moment_bytes_legacy_3x": 3 * p_bytes,
        "moment_savings_x": round(3 * p_bytes / max(moment_bytes, 1),
                                  2),
    }


def rows(archs=ARCHS + EXTRA_ARCHS):
    out = []
    for arch in archs:
        c = _wu_case(arch)
        for tag, v in c["variants"].items():
            out.append({
                "arch": arch, "variant": tag, **v,
                "bitwise_equal": c["bitwise_equal"].get(tag, True),
                "moment_bytes": c["moment_bytes"],
            })
    return out


# -- distributed INV→VMM comparison (forced 4-device child) -----------------

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import json

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import timed
from repro.configs import get_smoke_config
from repro.core import kfac
from repro.core.kfac import KFACConfig
from repro.dist.api import path_key
from repro.launch import steps as steps_mod
from repro.solve import make_wu_plan, refresh_and_precondition

arch = os.environ.get("REPRO_WU_ARCH", "qwen1.5-0.5b")
cfg = get_smoke_config(arch)
kcfg = KFACConfig(block_size=64, ns_iters=8, taylor_terms=3,
                  refine_steps=1)
mod = steps_mod.model_module(cfg)
specs = steps_mod.kfac_specs(cfg)
params = mod.init(cfg, jax.random.PRNGKey(0))
state = kfac.init(params, specs, kcfg)
r = np.random.default_rng(0)


def spd(x):
    bs = x.shape[-1]
    a = r.standard_normal(x.shape[:-1] + (2 * bs,)).astype(np.float32)
    return jnp.asarray(np.einsum("...ij,...kj->...ik", a, a) / (2 * bs))


factors = jax.tree.map(spd, state.factors)
grads = jax.tree.map(
    lambda p: jnp.asarray(r.standard_normal(p.shape).astype(np.float32)),
    params)
gbn = {path_key(p): g for p, g in
       jax.tree_util.tree_flatten_with_path(grads)[0]
       if path_key(p) in specs}

mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
wu = make_wu_plan(specs, factors, kcfg, ndev=4)

# legacy reference: replicated refresh + per-leaf precondition
ref_inv = jax.jit(
    lambda s: kfac.refresh_inverses(s, kcfg))(
        state._replace(factors=factors)).inverses
pre_ref = jax.jit(lambda g, s: kfac.precondition(g, s, specs, kcfg))(
    grads, state._replace(inverses=ref_inv))
ref_by = {path_key(p): np.asarray(v) for p, v in
          jax.tree_util.tree_flatten_with_path(pre_ref)[0]}

res = {"arch": arch, "ndev": 4, "total_tiles": wu.total_tiles}
with jax.set_mesh(mesh):
    for mode in ("gather", "owner"):
        fn = jax.jit(lambda f, g, mode=mode: refresh_and_precondition(
            f, g, kcfg, wu, mesh=mesh, mode=mode))
        (inv, pre), us = timed(fn, factors, gbn)
        ok = all(bool((np.asarray(a) == np.asarray(b)).all())
                 for a, b in zip(jax.tree.leaves(ref_inv),
                                 jax.tree.leaves(inv)))
        ok = ok and all(
            bool((np.asarray(pre[n]) == ref_by[n]).all()) for n in gbn)
        res[mode] = {"wall_ms": round(us / 1e3, 2),
                     "bitwise_equal": bool(ok)}
print(json.dumps(res))
"""


def dist_rows():
    here = os.path.dirname(__file__)
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD], capture_output=True, text=True,
        timeout=1800,
        env={**os.environ, "PYTHONPATH": os.pathsep.join((
            os.path.join(here, "..", "src"),
            os.path.join(here, "..")))})
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    d = json.loads(proc.stdout.strip().splitlines()[-1])
    assert d["gather"]["bitwise_equal"] and d["owner"]["bitwise_equal"]
    return d


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dist", action="store_true",
                    help="run ONLY the forced-4-device INV→VMM "
                         "dataflow comparison (gather vs owner) — the "
                         "local fused-vs-per-leaf sweep is the "
                         "default mode, so the multidevice CI job "
                         "does not repeat the tier-1 measurements")
    ap.add_argument("--out", default="BENCH_wu_fusion.json")
    args = ap.parse_args(argv)

    if args.dist:
        d = dist_rows()
        print_csv("wu_fusion_dist", [
            {"mode": m, **d[m]} for m in ("gather", "owner")])
        with open("BENCH_wu_fusion_dist.json", "w") as f:
            json.dump(d, f, indent=1)
        print("# wrote BENCH_wu_fusion_dist.json")
        return

    cases = [_wu_case(a) for a in ARCHS + EXTRA_ARCHS]
    table = []
    for c in cases:
        leg, fus = c["variants"]["per_leaf"], c["variants"]["fused"]
        assert c["bitwise_equal"]["fused"], \
            f"{c['arch']}: fused != per-leaf"
        assert c["bitwise_equal"]["fused+ew_pool"], \
            f"{c['arch']}: ew-pooled != per-leaf"
        if c["arch"] in ARCHS:      # the asserted acceptance archs
            # executed-program op count: strictly fewer MXU kernels
            # (dot) and fewer optimized-HLO entry ops; the raw jaxpr
            # eqn count is recorded but not asserted (pre-optimization
            # bookkeeping — reshape/concat eqns that XLA folds away)
            assert fus["hlo_dots"] < leg["hlo_dots"], c
            assert fus["hlo_ops"] < leg["hlo_ops"], c
            # wall: judged on the *paired* per-round difference (the
            # drift-robust estimator). On quiet hardware the fused
            # path wins by tens to hundreds of us on ~1-2ms steps,
            # but loaded shared runners swing the paired median by
            # +-7%, so the guard is 15%: wide enough not to flake,
            # tight enough to catch the failure modes this benchmark
            # rejected during development (index-gathered pools 1.4-
            # 2.8x, forced elementwise pooling 1.4-1.7x slower). The
            # deterministic executed-op counts above are the tracked
            # perf signal; the signed wall numbers are recorded.
            diff = c["paired"]["paired_diff_med_us"]
            assert diff >= -0.15 * leg["wall_ms"] * 1e3, (
                f"{c['arch']}: fused WU slower than per-leaf "
                f"(paired median {diff}us on {leg['wall_ms']}ms)")
        for tag, v in c["variants"].items():
            # moment_bytes is the *measured* slim per-path state every
            # variant ran with; the pre-slimming 3x-params layout is a
            # separate computed baseline column, not a measurement
            table.append({"arch": c["arch"], "variant": tag, **v,
                          "moment_bytes": c["moment_bytes"],
                          "moment_bytes_3x_baseline":
                              c["moment_bytes_legacy_3x"]})
    print_csv("wu_fusion", table)

    with open(args.out, "w") as f:
        json.dump({"cases": cases}, f, indent=1)
    print(f"# wrote {args.out}")


if __name__ == "__main__":
    main()
