"""Device time by the scopes of the latent-attention and expert-share
model (``models/lm._mla_block``, ``models/moe.share_ffn``,
``kernels/grouped_matmul``), read from a profiler trace by ``scopes.py``.

``scopes.SCOPES`` knows six scopes; this module adds ``mla``,
``moe_route``, ``moe_experts`` and ``expert_gram``, and a kernel flag:
an operation that is a Pallas call (``pallas_call`` in its name stack)
is charged to its scope and to that scope's kernels (``<scope>/kernel``).
``load`` is ``scopes.load`` run with this module's ``scope_of`` in place
of its own; everything else (the decoding, the window, the charging per
program) is ``scopes``'.

``split(reading)`` returns None, and never raises, where the trace cannot
be found or decoded, or where no operation carries a scope.
"""

from __future__ import annotations

import contextlib
import re
import traceback
from typing import Dict, Optional

import scopes

SCOPES = scopes.SCOPES + ("mla", "moe_route", "moe_experts", "expert_gram")
_COMPONENT = re.compile(r"^(?:\w+\()*(%s)\)*$" % "|".join(SCOPES))
#: the name-stack component of an operation a Pallas kernel lowers to
KERNEL = "pallas_call"
_KERNEL_TAG = "/kernel"
_scope_of = scopes.scope_of


def _is_kernel(stack: str) -> bool:
    return KERNEL in stack.split(";")[0].rsplit(":", 1)[0].split("/")


@contextlib.contextmanager
def _names(**more):
    """``scopes`` with this module's scope names (and ``more`` of its
    globals) in place of its own, for the time of the block."""
    swap = dict(_COMPONENT=_COMPONENT, **more)
    saved = {k: getattr(scopes, k) for k in swap}
    for k, v in swap.items():
        setattr(scopes, k, v)
    try:
        yield
    finally:
        for k, v in saved.items():
            setattr(scopes, k, v)


def scope_of(stack: str) -> Optional[str]:
    """The innermost scope of ``SCOPES`` in a name stack (``scopes``'
    rule over this module's names), tagged ``<scope>/kernel`` for a
    Pallas call; None for none."""
    with _names():
        found = _scope_of(stack)
    if found is not None and _is_kernel(stack):
        found += _KERNEL_TAG
    return found


def load(path: str) -> Optional[Dict[str, Dict[str, float]]]:
    """program -> scope (and ``<scope>/kernel``) -> device seconds in the
    traced window, averaged over the TPU devices."""
    with _names(scope_of=scope_of):
        got = scopes.load(path)
    if got is None:
        return None
    out = {}
    for program, by_scope in got.items():
        d = dict(by_scope)
        for k, v in by_scope.items():
            if k.endswith(_KERNEL_TAG):
                base = k[:-len(_KERNEL_TAG)]
                d[base] = d.get(base, 0.0) + v
        out[program] = d
    return out


def split(reading) -> Optional[Dict[str, Dict[str, float]]]:
    """``load`` of the run's trace, once per reading."""
    if "_moe_scopes" not in reading.__dict__:
        try:
            path = scopes.trace_path(reading)
            reading._moe_scopes = load(path) if path else None
        except Exception:
            # a reader returns None and never fails the run: say why
            traceback.print_exc()
            reading._moe_scopes = None
    return reading._moe_scopes


def seconds(reading, program: str, scope: str) -> Optional[float]:
    """Device seconds under ``scope`` (or ``<scope>/kernel``) in
    ``program``; None for none."""
    return (split(reading) or {}).get(program, {}).get(scope) or None


def ms_per(reading, program: str, scope: str, calls: int
           ) -> Optional[float]:
    """Device milliseconds under ``scope`` in ``program`` per call."""
    t = seconds(reading, program, scope)
    if not t or not calls:
        return None
    return 1e3 * t / calls
