"""Compile a cell's three programs (train, statistics, inverse refresh) for
one chip of a described TPU v5e, with no chip attached, and print what the
compiler's memory analysis says of each.

    JAX_PLATFORMS=cpu python bench/compile_v5e.py --workload <cell>

It lowers from shapes only (``jax.eval_shape``), so nothing is allocated.
The compiler counts one program at a time, not what the process keeps on
the device beside it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    import harness
    from repro.configs.base import ModelConfig
    from repro.core.kfac import KFACConfig
    from repro.dist import sharding as shard_rules
    from repro.launch import steps as steps_mod

    jax.config.update("jax_enable_compilation_cache", False)
    cell = harness.load_cell(args.workload)
    t = cell.traffic
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    devs = np.array(topo.devices[:cell.chips])
    mesh = Mesh(devs.reshape(-1, t["model_parallel"]), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    pcfg = dict(cell.config["program"], soi_block=t["block_size"])
    cfg = ModelConfig(**pcfg)
    kcfg = KFACConfig(block_size=cfg.soi_block, stats_every=t["stats_every"],
                      inv_every=t["inv_every"], stats_batch=t["batch"],
                      stats_seq=t["seq"], precision=t["precision"],
                      **t["kfac"])
    ab = steps_mod.abstract_train_state(cfg, kcfg)
    shard = steps_mod.TrainState(
        shard_rules.param_sharding(ab.params, mesh),
        shard_rules.kfac_sharding(ab.kfac, ab.params, mesh))
    batch = steps_mod.train_batch_sds(cfg, t["batch"], t["seq"])
    bshard = shard_rules.batch_sharding(batch, mesh)
    state = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sh), ab, shard)
    batch = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sh), batch, bshard)
    plan = steps_mod.make_wu_plan_for(cfg, kcfg, ndev=devs.size,
                                      abstract_state=ab)
    refresh = steps_mod.make_inv_refresh(cfg, kcfg, mesh=mesh,
                                         distributed=t["dist_inv"],
                                         abstract_state=ab)
    progs = {
        "train": (jax.jit(steps_mod.make_train_step(cfg, kcfg, wu_plan=plan),
                          donate_argnums=(0,)), (state, batch)),
        "stats": (jax.jit(steps_mod.make_stats_step(cfg, kcfg),
                          donate_argnums=(0,)), (state, batch)),
        "refresh": (jax.jit(lambda f, r: refresh(f), donate_argnums=(1,),
                            keep_unused=True),
                    (state.kfac.factors, state.kfac.inverses)),
    }
    out = {}
    with jax.set_mesh(mesh):
        for name, (fn, a) in progs.items():
            ma = fn.lower(*a).compile().memory_analysis()
            out[name] = {k: getattr(ma, k) for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "alias_size_in_bytes",
                "generated_code_size_in_bytes")}
            print(name, json.dumps(out[name]), flush=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
