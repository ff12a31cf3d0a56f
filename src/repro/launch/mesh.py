"""Production meshes.

Single pod: (data=16, model=16) — 256 chips (one v5e pod).
Multi-pod:  (pod=2, data=16, model=16) — 512 chips; ``pod`` is a pure
data-parallel outer axis so the only cross-pod (DCN) collective is the
once-per-step gradient all-reduce (optionally int8-compressed,
``dist/compression.py``).

Pipeline (``pp > 1``): a ``stage`` axis slots between ``pod`` and
``data`` — (pod, stage, data, model) — holding one contiguous layer
slice per stage (``repro.pipeline``). Stage is outer to ``data`` so the
per-tick ppermute transfers ride the fast intra-slice links while the
``pod`` boundary still only carries the per-step gradient all-reduce.

Functions, not module constants: importing this module must never touch
jax device state (smoke tests run on 1 CPU device; only
``launch/dryrun.py`` forces the 512-device host platform).
"""

from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the model code shards
    through ``shard_hint`` constraints and leaves propagation to GSPMD,
    which ``make_mesh``'s default ``Explicit`` axes would refuse."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axes))


def make_production_mesh(*, multi_pod: bool = False, pp: int = 1,
                         model: int = 16):
    """The full (pod, stage, data, model) layout on 256/512 chips.

    ``model`` resizes the inner TP axis (freed chips widen ``data``);
    ``pp`` splits the data axis into (stage, data). Defaults reproduce
    the classic (16, 16) / (2, 16, 16) pods."""
    if 256 % model:
        raise ValueError(f"model={model} does not divide the 256-chip "
                         f"pod slice")
    shape = (2, 256 // model, model) if multi_pod \
        else (256 // model, model)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    if pp > 1:
        d = shape[-2]
        if d % pp:
            raise ValueError(
                f"pp={pp} does not divide the data axis ({d})")
        shape = shape[:-2] + (pp, d // pp, shape[-1])
        axes = axes[:-2] + ("stage", "data", "model")
    return _auto_mesh(shape, axes)


def make_dev_mesh(model: int = 1):
    """Largest (data, model) mesh on the local device pool (CPU tests,
    single-host runs)."""
    n = jax.device_count()
    if n % model:
        raise ValueError(f"{n} devices not divisible by model={model}")
    return _auto_mesh((n // model, model), ("data", "model"))


def make_pipeline_mesh(pp: int, model: int = 1):
    """Largest (stage, data, model) mesh on the local device pool.

    ``stage`` is the pipeline axis consumed by ``repro.pipeline``'s
    shard_map program; ``model`` is the in-stage megatron-TP / EP axis
    (the stage program slices eligible weights over it); the leftover
    devices data-parallel the microbatch rows."""
    n = jax.device_count()
    if pp < 1:
        raise ValueError(f"pp must be >= 1, got {pp}")
    if n % (pp * model):
        raise ValueError(
            f"{n} devices not divisible by pp={pp} * model={model}")
    return _auto_mesh((pp, n // (pp * model), model),
                      ("stage", "data", "model"))
