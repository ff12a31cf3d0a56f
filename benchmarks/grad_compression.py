"""DCN-crossing gradient all-reduce: fp32 vs int8 error-feedback
compression (DESIGN.md §5), measured from lowered HLO on the multi-pod
mesh.

At 512 chips the only cross-pod collective is the once-per-step
gradient all-reduce over the ``pod`` axis (DCN, ~10x scarcer bandwidth
than ICI). ``dist/compression.py`` quantizes the summand to int8 with
an error-feedback buffer; here we lower both variants for a
llama3.2-1b-sized gradient tree and count the collective bytes XLA
actually schedules.

Run: PYTHONPATH=src python -m benchmarks.grad_compression
(requires the 512-device dry-run env; spawned as a subprocess with the
flag set, like launch/dryrun.py). For a reduced probe that still
crosses a real 2-way ``pod`` axis (CI / laptops), set
``REPRO_GC_DEVICES=2`` — the child then builds a (pod=2, data=N/2,
model=1) mesh instead of the production (2, 16, 16).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import print_csv

_CHILD = r"""
import os
_NDEV = int(os.environ.get("REPRO_GC_DEVICES", "512"))
if _NDEV < 512:
    # reduced-probe mesh is (2, N//2, 1): clamp to an even count >= 2
    # so the forced device pool matches the mesh size exactly
    _NDEV = max(2, _NDEV - (_NDEV % 2))
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=%d" % _NDEV)
os.environ["JAX_PLATFORMS"] = "cpu"
import json
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.dist import sharding as shard_rules
from repro.dist.compression import compressed_psum, init_error_buffers
from repro.launch import hlo_analysis
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_production_mesh

if _NDEV >= 512:
    mesh = make_production_mesh(multi_pod=True)
else:
    # reduced probe: keep the DCN-crossing pod axis, shrink the rest
    mesh = jax.make_mesh((2, _NDEV // 2, 1), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
cfg = get_config(os.environ.get("REPRO_GC_ARCH", "llama3.2-1b"))
params = steps_mod.abstract_params(cfg)
pshard = shard_rules.param_sharding(params, mesh)


def plain(grads):
    # baseline: fp32 mean over the pod axis (what DP inserts)
    return jax.tree.map(
        lambda g: jax.lax.pmean(g.astype(jnp.float32), "pod"), grads)


def compressed(args):
    grads, errors = args
    out, errs = {}, {}
    flat_g = jax.tree_util.tree_flatten_with_path(grads)[0]
    flat_e = jax.tree.leaves(errors)
    o_leaves, e_leaves = [], []
    for (path, g), e in zip(flat_g, flat_e):
        m, ne = compressed_psum(g, e, ("pod",))
        o_leaves.append(m)
        e_leaves.append(ne)
    td = jax.tree_util.tree_structure(grads)
    return (jax.tree_util.tree_unflatten(td, o_leaves),
            jax.tree_util.tree_unflatten(td, e_leaves))


def specs_like(tree, mesh):
    # per-leaf in/out specs matching the param sharding minus 'pod'
    def spec_of(s):
        parts = tuple(p if p != "pod" else None
                      for p in (s.spec + (None,) * 8)[:8])
        return P()  # gradients replicated within pod for this probe
    return jax.tree.map(lambda _: P(), tree)


with jax.set_mesh(mesh):
    from jax import shard_map

    grads = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), params)
    errors = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), params)

    out = {}
    fn_plain = shard_map(plain, mesh=mesh,
                         in_specs=(specs_like(grads, mesh),),
                         out_specs=specs_like(grads, mesh),
                         check_vma=False)
    c = jax.jit(fn_plain).lower(grads).compile()
    mc = hlo_analysis.analyze_text(c.as_text())
    out["fp32"] = {k: int(v) for k, v in mc.coll.items()}

    fn_c = shard_map(compressed, mesh=mesh,
                     in_specs=((specs_like(grads, mesh),
                                specs_like(errors, mesh)),),
                     out_specs=(specs_like(grads, mesh),
                                specs_like(errors, mesh)),
                     check_vma=False)
    c2 = jax.jit(fn_c).lower((grads, errors)).compile()
    mc2 = hlo_analysis.analyze_text(c2.as_text())
    out["int8_ef"] = {k: int(v) for k, v in mc2.coll.items()}

print(json.dumps(out))
"""


def rows():
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD], capture_output=True, text=True,
        timeout=3600,
        env={**os.environ, "PYTHONPATH": os.path.join(
            os.path.dirname(__file__), "..", "src")})
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    data = json.loads(proc.stdout.strip().splitlines()[-1])
    out = []
    for variant, coll in data.items():
        total = sum(coll.values())
        out.append({"variant": variant,
                    "coll_bytes_per_dev": total,
                    "all_reduce": coll.get("all-reduce", 0)})
    if len(out) == 2:
        a, b = out[0], out[1]
        out.append({"variant": "reduction_x",
                    "coll_bytes_per_dev": round(
                        a["coll_bytes_per_dev"]
                        / max(b["coll_bytes_per_dev"], 1), 2),
                    "all_reduce": ""})
    return out


def main():
    print_csv("grad_compression_dcn", rows())


if __name__ == "__main__":
    main()
