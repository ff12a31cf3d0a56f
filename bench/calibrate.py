"""Readings that the limits of ``bench/limits/<cell>.json`` are set from,
on the chip, in one process (the programs compile once), each seed's
reference run right after the program's:

- the program against the reference on each of ``--seeds`` (the lower
  readings);
- the control, the reference computed one precision step lower, against
  the reference on each of ``--control-seeds``;
- each planted fault against the reference on each of ``--fault-seeds``:
  half of every batch left out (the mean taken over the rest) and, on
  more than one chip, the owner exchange of the distributed refresh left
  out;
- with ``--witness``, on each of ``--seeds``, how far the program's
  inverses after the checked steps lie from the exact (float32 LU at
  ``highest``) inverses of its own damped factors, per factor leaf.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 1,2,3 --fault-seeds 1,2,3 --out <file.json>

``--chips`` runs a ``<config>.<traffic>`` pair that BENCHMARK.json does
not list; ``--kfac key=value`` overrides an optimizer setting of the
traffic file (``inv_method=composed`` runs the program's composed-precision
inverse in place of the exact one the traffic files state).

The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from typing import Dict, Sequence


def _gaps(gaps) -> Dict[str, float]:
    return {k: v[0] for k, v in gaps.items()}


def inverse_errors(factors, inverses, damping):
    """Per factor leaf: |X - (F + lam I)^-1| / |(F + lam I)^-1| with the
    exact inverse in float32 LU at ``highest``."""
    import jax
    import jax.numpy as jnp

    out = {}
    with jax.default_matmul_precision("highest"):
        for name, f in factors.items():
            for side, a in f.items():
                bs = a.shape[-1]
                lam = damping * jnp.trace(a, axis1=-2, axis2=-1) / bs + 1e-8
                exact = jnp.linalg.inv(a + lam[..., None, None]
                                       * jnp.eye(bs, dtype=a.dtype))
                x = inverses[name][side + "_inv"]
                out[f"{name}/{side}"] = (jnp.linalg.norm(x - exact)
                                         / jnp.linalg.norm(exact))
    return out


def calibrate(harness, cell, devices, seeds: Sequence[int],
              control_seeds: Sequence[int] = (),
              fault_seeds: Sequence[int] = (), witness: bool = False,
              log=print) -> dict:
    """Seed by seed, as in a run: the program's first steps, its state
    freed, then the reference over the same steps."""
    import jax

    import check

    errors = jax.jit(inverse_errors, static_argnums=2)
    reference = harness.Reference(cell)

    def program_gaps(faults, seed_list, wit=False):
        out, wits = {}, {}
        tag = "+".join(faults) or "program"
        prog = harness.Program(cell, devices, faults=faults)
        try:
            for s in seed_list:
                t0 = time.perf_counter()
                state, feed, nums, batch, inv = prog.start(s)
                nums = check.to_host(nums)
                if wit:
                    e = check.to_host(errors(state.kfac.factors,
                                             state.kfac.inverses,
                                             prog.kcfg.damping))
                    worst = max(e, key=e.get)
                    wits[s] = {"worst": worst, "value": e[worst], "all": e}
                    log(f"{cell.name} inverse witness seed {s}: worst "
                        f"{worst} {e[worst]!r}")
                del state, feed, batch
                gc.collect()
                out[s] = _gaps(check.compare(nums, reference.numbers(s, inv)))
                del inv
                log(f"{cell.name} {tag} seed {s} "
                    f"({time.perf_counter() - t0:.1f} s): {out[s]}")
        finally:
            prog.close()
        return out, wits

    faults = [("half_batch",)] + ([("exchange",)] if cell.chips > 1 else [])
    prog_gaps, wits = program_gaps((), seeds, witness)
    res = {"cell": cell.name, "kfac": cell.traffic["kfac"],
           "program": prog_gaps, "witness": wits,
           "faults": {"+".join(f): program_gaps(f, fault_seeds)[0]
                      for f in faults if fault_seeds}}
    if control_seeds:
        control = harness.Reference(cell, "control")
        res["control"] = {}
        for s in control_seeds:
            nums = control.numbers(s, keep=True)
            inv = nums.pop("inverses")
            res["control"][s] = _gaps(check.compare(
                nums, reference.numbers(s, inv)))
            del inv
            log(f"{cell.name} control seed {s}: {res['control'][s]}")
    return res


def _ints(s: str):
    return [int(x) for x in s.split(",") if x]


def main(argv=None) -> int:
    import run

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, default=[])
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--fault-seeds", type=_ints, default=[])
    ap.add_argument("--witness", action="store_true")
    ap.add_argument("--chips", type=int, default=None)
    ap.add_argument("--kfac", action="append", default=[])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    harness, cell, devices, _ = run.prepare(args.workload, args.chips)
    for kv in args.kfac:
        k, v = kv.split("=", 1)
        cell.traffic["kfac"][k] = v
    res = calibrate(harness, cell, devices, args.seeds, args.control_seeds,
                    args.fault_seeds, args.witness, log=run.log)
    with open(args.out, "w") as f:
        json.dump(res, f, indent=1)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
