"""jit'd public wrappers for the Pallas kernels.

On a TPU backend the kernels compile to Mosaic; on any other backend
(the CPU test suite) they run under ``interpret=True``, which executes
the same kernel body block by block in Python. The wrappers decide the
mode from the backend alone, so a TPU run never falls back to the
interpreter.

No default K-FAC path calls a kernel: ``precondition(use_kernel=True)``
(``fused_precond``) and ``SMWConfig(use_kernel=True)`` (``smw_update``)
opt in; the composed-precision SOI inversion runs as plain JAX
(``core.precision_inv.composed_inverse``), the same algorithm as
``neumann_inv``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.bitslice_mm import bitslice_mm as _bitslice_mm
from repro.kernels.fused_gram_solve import fused_gram_inv as _fused_gram_inv
from repro.kernels.fused_precond import fused_precond as _fused_precond
from repro.kernels.neumann_inv import neumann_inv as _neumann_inv
from repro.kernels.smw_update import smw_update as _smw_update

__all__ = ["bitslice_mm", "neumann_inv", "fused_gram_inv",
           "fused_precond", "smw_update", "on_tpu"]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def bitslice_mm(a: jax.Array, b: jax.Array, **kw) -> jax.Array:
    return _bitslice_mm(a, b, interpret=not on_tpu(), **kw)


def neumann_inv(a: jax.Array, damping, **kw) -> jax.Array:
    return _neumann_inv(a, jnp.asarray(damping), interpret=not on_tpu(),
                        **kw)


def fused_gram_inv(a: jax.Array, **kw) -> jax.Array:
    return _fused_gram_inv(a, interpret=not on_tpu(), **kw)


def fused_precond(a_inv: jax.Array, g: jax.Array, g_inv: jax.Array,
                  **kw):
    return _fused_precond(a_inv, g, g_inv, interpret=not on_tpu(), **kw)


def smw_update(inv: jax.Array, v: jax.Array, *, decay: float,
               cscale: float, **kw) -> jax.Array:
    return _smw_update(inv, v, decay=decay, cscale=cscale,
                       interpret=not on_tpu(), **kw)
