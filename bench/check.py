"""The comparison that decides ``correct``.

The first steps of a run go through the timed step function; the plain
reference (``bench/reference``) follows the same steps on the same rows.
Compared, each against its limit from ``bench/limits/<cell>.json``:

- ``loss``: the largest relative gap of a step's training loss, and of
  the statistics pass's loss where one ran, over the checked steps;
- ``grad``: the first gradient as the optimizer holds it after step 1
  (the momentum ``nu A^-1 g G^-1`` of each factored weight, Adam's
  ``mu / (1 - b1)`` of every other parameter);
- ``factor``: the SU factors after step 1;
- ``inverse``: the INV block inverses after step 1, block by block:
  ``|X - X_ref| / |X_ref|`` (Frobenius) of the worst block of any
  factor, so a wrong direction shows, not only a wrong size;
- ``change``: each parameter's change over the checked steps.

``grad``, ``factor`` and ``change`` are the worst leaf's gap between the
program's norm and the reference's, over the larger of the reference's
norm of that leaf and of the median leaf. ``change`` leaves out leaves
whose reference gradient at step 1 is under a thousandth of the median
leaf's: they move under Adam by round-off alone.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp


#: the compared numbers, in the order they are printed
NAMES = ("loss", "grad", "factor", "inverse", "change")
#: gradients under this share of the median leaf's do not count
NOUGHT = 1e-3


def path_of(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def leaves_by_path(tree) -> Dict[str, jax.Array]:
    return {path_of(p): x for p, x in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def norm(x):
    x = x.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(x * x))


@jax.jit
def tree_norms(tree) -> Dict[str, jax.Array]:
    return {k: norm(v) for k, v in leaves_by_path(tree).items()}


@functools.partial(jax.jit, static_argnums=(3, 4))
def first_norms(momentum, adam_mu, factors, factored, b1):
    """Norms of the optimizer state after step 1 (see module doc)."""
    mom = leaves_by_path(momentum)
    mu = leaves_by_path(adam_mu)
    grad = {k: (mom[k] if k in factored else mu[k] / (1.0 - b1))
            for k in mom}
    return {"grad": {k: norm(v) for k, v in grad.items()},
            "factor": tree_norms(factors)}


@jax.jit
def inverse_gaps(inverses, ref_inverses) -> Dict[str, jax.Array]:
    """Per inverse leaf ``(..., bs, bs)``: the worst block's
    ``|X - X_ref| / |X_ref|``."""
    x, r = leaves_by_path(inverses), leaves_by_path(ref_inverses)

    def gap(a, b):
        d = jnp.sqrt(jnp.sum(jnp.square(a - b), axis=(-2, -1)))
        return jnp.max(d / jnp.sqrt(jnp.sum(b * b, axis=(-2, -1))))

    return {k: gap(x[k], r[k]) for k in r}


def worst_leaf(gaps: Dict[str, float]) -> Tuple[float, str]:
    leaf = max(gaps, key=lambda k: gaps[k] if math.isfinite(gaps[k])
               else math.inf)
    return (gaps[leaf] if math.isfinite(gaps[leaf]) else math.inf), leaf


@jax.jit
def change_norms(params, p0) -> Dict[str, jax.Array]:
    a, b = leaves_by_path(params), leaves_by_path(p0)
    return {k: norm(a[k] - b[k]) for k in a}


def leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
             skip: Sequence[str] = ()) -> Tuple[float, str]:
    """Worst ``|prog - ref| / max(ref, median ref)`` over leaves."""
    keys = [k for k in ref if k not in skip]
    if set(prog) != set(ref):
        return math.inf, "leaf sets differ"
    med = sorted(ref[k] for k in keys)[len(keys) // 2]
    worst, leaf = 0.0, ""
    for k in keys:
        p = prog[k]
        g = abs(p - ref[k]) / max(ref[k], med) if math.isfinite(p) \
            else math.inf
        if not g <= worst:
            worst, leaf = g, k
    return worst, leaf


def nought_leaves(grad0: Dict[str, float]) -> Tuple[str, ...]:
    med = sorted(grad0.values())[len(grad0) // 2]
    return tuple(sorted(k for k, v in grad0.items() if v < NOUGHT * med))


def compare(prog: dict, ref: dict) -> Dict[str, Tuple[float, str]]:
    """Gaps of every compared number: name -> (gap, where)."""
    out = {}
    worst, at = 0.0, ""
    for kind in ("losses", "stats_losses"):
        for i, r in ref[kind].items():
            p = prog[kind].get(i, math.nan)
            g = abs(p - r) / abs(r) if math.isfinite(p) else math.inf
            if not g <= worst:
                worst, at = g, f"{kind[:-2]} {i}"
    if set(prog["stats_losses"]) != set(ref["stats_losses"]):
        worst, at = math.inf, "statistics passes differ"
    out["loss"] = (worst, at)
    for name in ("grad", "factor"):
        out[name] = leaf_gap(prog["first"][name], ref["first"][name])
    # worked out during the reference's run, against the program's
    # inverses (``Reference.numbers``)
    out["inverse"] = worst_leaf(ref["inverse"]) if ref.get("inverse") \
        else (math.inf, "no inverses compared")
    out["change"] = leaf_gap(prog["change"], ref["change"],
                             skip=nought_leaves(ref["grad0"]))
    return out


def verdict(gaps: Dict[str, Tuple[float, str]], limits: Dict[str, float],
            extra_ok: bool = True) -> Tuple[bool, Dict[str, dict]]:
    """``correct`` and the printed table: name -> value, limit, where."""
    table = {}
    ok = extra_ok
    for name in NAMES:
        if name not in limits:
            ok = False          # a number with no limit holds nothing
            continue
        g, at = gaps[name]
        table[name] = {"value": g, "limit": limits[name], "at": at}
        ok = ok and g <= limits[name]
    return ok, table


def to_host(tree) -> dict:
    """Device scalars -> Python floats, in one transfer."""
    return jax.tree.map(float, jax.device_get(tree))
