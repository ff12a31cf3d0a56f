"""Elastic re-meshing on device loss.

When a pod/host drops, the surviving devices re-form the largest mesh
that (a) preserves the ``model`` axis (TP degree is baked into layouts
and SOI block sharding) and (b) keeps a power-of-two ``data`` axis so
the global batch still divides. Checkpoint restore then reshards every
array onto the new mesh (``checkpoint.restore(sharding_fn=...)``), and
training resumes from the last step — the same recovery path as a full
restart, minus the cold init.

``DeviceLoss`` is the injected-fault stand-in used by tests and the
failure drill in ``launch/train.py --inject-failure``: on real clusters
the equivalent signal is a NCCL/ICI timeout or the platform's
preemption notice.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence

import jax
from jax.sharding import Mesh


class DeviceLoss(RuntimeError):
    """Raised when part of the device pool is gone."""

    def __init__(self, lost: int, msg: str = ""):
        self.lost = lost
        super().__init__(msg or f"lost {lost} devices")


def _pow2_floor(n: int) -> int:
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


def largest_mesh(
    n_devices: int,
    model: int,
    *,
    pp: int = 1,
    axis_names: Sequence[str] = ("data", "model"),
) -> tuple:
    """Largest (data, model) — or, with ``pp > 1``,
    (stage, data, model) — shape with data a power of two. Like the
    ``model`` axis, the ``stage`` degree is preserved across re-meshes
    (the stage partition is baked into layouts and the pipeline
    schedule); only ``data`` shrinks on device loss."""
    if n_devices < model * pp:
        raise DeviceLoss(0, f"cannot keep model={model} x pp={pp} "
                            f"with {n_devices} devices")
    data = _pow2_floor(n_devices // (model * pp))
    if pp > 1:
        return (pp, data, model)
    return (data, model)


def elastic_mesh(
    model: int = 1,
    *,
    pp: int = 1,
    devices: Optional[Sequence] = None,
    exclude: int = 0,
    obs: Any = None,
) -> Mesh:
    """Build the largest healthy (data, model) mesh — with ``pp > 1``,
    a (stage, data, model) pipeline mesh (repro.pipeline).

    ``exclude`` drops that many devices from the tail of the pool —
    the test/drill hook for simulating a lost host. ``obs`` (a
    ``repro.obs.Observability``) records every (re-)mesh as an event +
    counter, so elastic shrinkage is visible in the telemetry stream.
    """
    devs = list(devices if devices is not None else jax.devices())
    if exclude:
        devs = devs[: len(devs) - exclude]
    if not devs:
        raise DeviceLoss(exclude, "no devices left")
    shape = largest_mesh(len(devs), model, pp=pp)
    import math

    import numpy as np
    n = math.prod(shape)
    arr = np.array(devs[:n]).reshape(shape)
    names = ("stage", "data", "model") if pp > 1 else ("data", "model")
    if obs is not None and getattr(obs, "enabled", False):
        obs.counter("runtime_remesh_total",
                    "mesh (re-)formations, recoveries included").inc()
        obs.event("remesh", shape=dict(zip(names, shape)),
                  n_devices=n, excluded=exclude)
    return Mesh(arr, names,
                axis_types=(jax.sharding.AxisType.Auto,) * len(names))
