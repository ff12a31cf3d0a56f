"""Distributed SOI inversion: per-device inverse work vs mesh size.

The paper's scaling claim for the INV engine (Sec. IV-B): inversion
latency shrinks with the number of INV crossbar groups because factor
blocks are distributed across them. The TPU analogue is
``repro.solve``: on an ``ndev``-device mesh each device inverts only
its plan-owned blocks, so per-device inverted-block count drops from
``total`` (replicated ``kfac.refresh_inverses``) to
``<= ceil(total/ndev)``.

Run: PYTHONPATH=src python -m benchmarks.dist_inverse
(spawns a child with a forced 4-device host platform, like
benchmarks/grad_compression.py; REPRO_DI_DEVICES / REPRO_DI_ARCH tune
the probe). The child asserts numerical parity of the two paths and
the per-device block-count bound; the parent prints the CSV.

``--smw`` adds the incremental-SOI probe (repro.solve.smw / pdiv):
per-step SMW refresh wall vs a full re-inversion at bs=256 (asserted
>= 3x apart), exactness drift over a simulated EMA trajectory with the
fallback gate, and the divide-and-conquer inversion of a block 2x one
device's pool share (asserted bitwise local == distributed). Results
land in ``BENCH_dist_inverse.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

from benchmarks.common import print_csv

_CHILD = r"""
import os
_NDEV = int(os.environ.get("REPRO_DI_DEVICES", "4"))
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=%d" % _NDEV)
os.environ["JAX_PLATFORMS"] = "cpu"
import json

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import timed
from repro.configs import get_smoke_config
from repro.core.kfac import KFACConfig
from repro.launch import steps as steps_mod
from repro.solve import invert_factor_tree, make_plan

arch = os.environ.get("REPRO_DI_ARCH", "qwen1.5-0.5b")
cfg = get_smoke_config(arch)
kcfg = KFACConfig(block_size=64, ns_iters=8, taylor_terms=3,
                  refine_steps=1)
specs = steps_mod.kfac_specs(cfg)

from repro.core import soi
shapes = jax.eval_shape(lambda: soi.init_factors(specs, kcfg.block_size))

r = np.random.default_rng(0)


def spd(s):
    bs = s.shape[-1]
    a = r.standard_normal(s.shape[:-1] + (2 * bs,)).astype(np.float32)
    g = np.einsum("...ij,...kj->...ik", a, a) / (2 * bs)
    return jnp.asarray(g)


factors = jax.tree.map(spd, shapes)

# 2D mesh when the forced pool splits evenly, flat data mesh otherwise
# (REPRO_DI_DEVICES=1 or odd counts)
if _NDEV > 1 and _NDEV % 2 == 0:
    mesh_shape, mesh_axes = (2, _NDEV // 2), ("data", "model")
else:
    mesh_shape, mesh_axes = (_NDEV,), ("data",)
mesh = jax.make_mesh(
    mesh_shape, mesh_axes,
    axis_types=(jax.sharding.AxisType.Auto,) * len(mesh_shape))
plan = make_plan(factors, _NDEV, kcfg)

rep = jax.jit(lambda f: invert_factor_tree(f, kcfg))
dist = jax.jit(lambda f: invert_factor_tree(f, kcfg, mesh=mesh,
                                            plan=plan))


inv_rep, us_rep = timed(rep, factors)
with jax.set_mesh(mesh):
    inv_dist, us_dist = timed(dist, factors)
ms_rep, ms_dist = us_rep / 1e3, us_dist / 1e3

# numerical parity (bitwise on the default composed method)
ra = jax.tree.leaves(inv_rep)
da = jax.tree.leaves(inv_dist)
assert all(bool((np.asarray(x) == np.asarray(y)).all())
           for x, y in zip(ra, da)), "distributed != replicated"

s = plan.summary()
# count bound: ceil(total/ndev) holds when every block costs the same
# (single block size -> the greedy round-robins); with mixed sizes LPT
# balances FLOPs instead and only the per-group ceiling sum is
# guaranteed (partition.py docstring)
uniform = len({g.bs for g in plan.groups}) == 1
if uniform:
    bound = -(-plan.total_blocks // _NDEV)
else:
    bound = sum(-(-g.n_blocks // _NDEV) for g in plan.groups)
assert plan.max_device_blocks <= bound, s
print(json.dumps({
    "arch": arch, "ndev": _NDEV,
    "total_blocks": s["total_blocks"],
    "device_blocks": s["device_blocks"],
    "device_gflops": s["device_gflops"],
    "count_bound": bound,
    "uniform_bs": uniform,
    "ms_replicated": round(ms_rep, 2),
    "ms_distributed": round(ms_dist, 2),
    "bitwise_equal": True,
}))
"""


_SMW_CHILD = r"""
import os
_NDEV = int(os.environ.get("REPRO_DI_DEVICES", "4"))
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=%d" % _NDEV)
os.environ["JAX_PLATFORMS"] = "cpu"
import json

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import timed
from repro.core.kfac import KFACConfig
from repro.solve import (SMWConfig, invert_factor_tree, pdiv_invert,
                         probe_drift, smw_refresh)

BS = int(os.environ.get("REPRO_DI_SMW_BS", "256"))
K = int(os.environ.get("REPRO_DI_SMW_K", "32"))
STEPS = int(os.environ.get("REPRO_DI_SMW_STEPS", "15"))
# production-quality composed inversion: the full-reinversion wall the
# SMW step is measured against is the one the double-buffered path
# actually dispatches each inv cadence
kcfg = KFACConfig(block_size=BS, ns_iters=12, taylor_terms=4,
                  refine_steps=2)
scfg = SMWConfig(drift_budget=0.05, rank=K)
r = np.random.default_rng(0)


def spd(shape):
    n = shape[-1]
    a = r.standard_normal(shape[:-1] + (2 * n,)).astype(np.float32)
    return jnp.asarray(
        np.einsum("...ij,...kj->...ik", a, a) / (2 * n))


# four bs=256 G blocks over two leaves — the geometry one transformer
# layer's output factor produces at soi_block=256
factors = {"lin0": {"G": spd((2, BS, BS))},
           "lin1": {"G": spd((2, BS, BS))}}


def cols_like(seed):
    rr = np.random.default_rng(seed)
    return {name: {"G": jnp.asarray(
        rr.standard_normal((2, K, BS)).astype(np.float32)
        / np.sqrt(K, dtype=np.float32))} for name in factors}


full = jax.jit(lambda f: invert_factor_tree(f, kcfg))
d_ema = kcfg.ema_decay


def ema_fn(f, c):
    # the contribution the SMW update models exactly: w = 1 (G side)
    return {name: {"G": d_ema * f[name]["G"] + (1.0 - d_ema)
                   * jnp.einsum("nkb,nkc->nbc", c[name]["G"],
                                c[name]["G"])} for name in f}


ema = jax.jit(ema_fn)
smw_step = jax.jit(
    lambda inv, f, c: smw_refresh(inv, f, c, kcfg, scfg))

inv = full(factors)
drift_base = float(probe_drift(factors, inv, kcfg))
assert drift_base <= scfg.drift_budget, (
    "full composed inversion already outside the drift budget: "
    "%g" % drift_base)

_, us_full = timed(full, factors)
c0 = cols_like(1)
f1 = ema(factors, c0)
(_, _), us_smw = timed(smw_step, inv, f1, c0)
assert us_smw * 3 <= us_full, (
    "SMW refresh %.0fus not >=3x below full re-inversion %.0fus"
    % (us_smw, us_full))

n_fallbacks = 0
drift_max = 0.0
for t in range(STEPS):
    c = cols_like(100 + t)
    factors = ema(factors, c)
    inv, drift = smw_step(inv, factors, c)
    d = float(drift)
    drift_max = max(drift_max, d)
    if not (d <= scfg.drift_budget):
        inv = full(factors)
        n_fallbacks += 1
drift_final = float(probe_drift(factors, inv, kcfg))
assert drift_final <= scfg.drift_budget, drift_final

# pdiv: one block 2x a device's pool share (2*BS vs one BS block per
# device), inverted across the mesh
if _NDEV > 1 and _NDEV % 2 == 0:
    mesh_shape, mesh_axes = (2, _NDEV // 2), ("data", "model")
else:
    mesh_shape, mesh_axes = (_NDEV,), ("data",)
mesh = jax.make_mesh(
    mesh_shape, mesh_axes,
    axis_types=(jax.sharding.AxisType.Auto,) * len(mesh_shape))
blk = spd((2 * BS, 2 * BS))
lam = 0.03
ploc = jax.jit(lambda b: pdiv_invert(b, lam, kcfg, depth=1))
pdst = jax.jit(lambda b: pdiv_invert(b, lam, kcfg, depth=1, mesh=mesh))
out_loc, us_ploc = timed(ploc, blk)
with jax.set_mesh(mesh):
    out_dst, us_pdst = timed(pdst, blk)
pdiv_bitwise = bool((np.asarray(out_loc) == np.asarray(out_dst)).all())
assert pdiv_bitwise, "pdiv distributed != local"

print(json.dumps({
    "bs": BS, "k": K, "ndev": _NDEV, "steps": STEPS,
    "ms_full_reinversion": round(us_full / 1e3, 2),
    "ms_smw_step": round(us_smw / 1e3, 2),
    "smw_speedup": round(us_full / us_smw, 1),
    "drift_budget": scfg.drift_budget,
    "drift_base": drift_base,
    "drift_max": drift_max,
    "drift_final": drift_final,
    "n_fallbacks": n_fallbacks,
    "ms_pdiv_local": round(us_ploc / 1e3, 2),
    "ms_pdiv_dist": round(us_pdst / 1e3, 2),
    "pdiv_block": 2 * BS,
    "pdiv_bitwise": pdiv_bitwise,
}))
"""


def _child_env():
    return {**os.environ, "PYTHONPATH": os.pathsep.join((
        os.path.join(os.path.dirname(__file__), "..", "src"),
        os.path.join(os.path.dirname(__file__), "..")))}


def _run_child(code):
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=1800, env=_child_env())
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    return json.loads(proc.stdout.strip().splitlines()[-1])


def rows(payload=None):
    ndev = int(os.environ.get("REPRO_DI_DEVICES", "4"))
    d = _run_child(_CHILD)
    if payload is not None:
        payload["dist"] = d
    total = d["total_blocks"]
    bound = d["count_bound"]
    out = [{
        "variant": "replicated",
        "blocks_per_dev": total,
        "gflops_per_dev": round(sum(d["device_gflops"]), 3),
        "wall_ms": d["ms_replicated"],
    }, {
        "variant": "distributed",
        "blocks_per_dev": max(d["device_blocks"]),
        "gflops_per_dev": max(d["device_gflops"]),
        "wall_ms": d["ms_distributed"],
    }, {
        "variant": (f"bound_ceil(total/{ndev})" if d["uniform_bs"]
                    else "bound_sum_group_ceils"),
        "blocks_per_dev": bound,
        "gflops_per_dev": "",
        "wall_ms": "",
    }]
    assert max(d["device_blocks"]) <= bound, d
    assert d["bitwise_equal"]
    return out


def smw_rows(payload=None):
    """Incremental-SOI probe: refresh wall + drift vs the full
    re-inversion the double-buffered baseline dispatches per cadence,
    plus the divide-and-conquer oversized-block inversion."""
    d = _run_child(_SMW_CHILD)
    if payload is not None:
        payload["smw"] = d
    return [{
        "variant": "full_reinversion (dispatched per inv cadence)",
        "wall_ms": d["ms_full_reinversion"],
        "drift": d["drift_base"],
        "note": f"bs={d['bs']} composed",
    }, {
        "variant": "smw_step (every step)",
        "wall_ms": d["ms_smw_step"],
        "drift": d["drift_max"],
        "note": f"k={d['k']} {d['smw_speedup']}x faster, "
                f"{d['n_fallbacks']}/{d['steps']} fallbacks, "
                f"final drift {d['drift_final']:.4f} <= "
                f"{d['drift_budget']}",
    }, {
        "variant": f"pdiv_local (block {d['pdiv_block']})",
        "wall_ms": d["ms_pdiv_local"],
        "drift": "",
        "note": "2x one device's pool share",
    }, {
        "variant": f"pdiv_distributed (block {d['pdiv_block']})",
        "wall_ms": d["ms_pdiv_dist"],
        "drift": "",
        "note": f"ndev={d['ndev']} bitwise == local",
    }]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smw", action="store_true",
                    help="also run the incremental-SOI (SMW + pdiv) "
                         "probe")
    args = ap.parse_args(argv)
    payload = {}
    print_csv("dist_inverse", rows(payload))
    if args.smw:
        print_csv("dist_inverse_smw", smw_rows(payload))
    with open("BENCH_dist_inverse.json", "w") as f:
        json.dump(payload, f, indent=1)


if __name__ == "__main__":
    main()
