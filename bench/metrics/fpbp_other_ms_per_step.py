"""Device time of the train program outside the ``attn``, ``mlp``,
``head`` and ``wu`` scopes, per step: embedding, norms, the layer scan's
stacking, gradient accumulation, unscoped operations and the program's
own gaps. With the four scoped parts it adds up to
``fpbp_wu_ms_per_step``."""

import scopes

#: the train program's name in the trace
MODULE = r"^jit_train_step\b"
PARTS = ("attn", "mlp", "head", "wu")


def read(r):
    tr = r.trace
    total = tr.module_s(MODULE) if tr is not None else None
    parts = scopes.split(r)
    if total is None or parts is None or not r.window.steps:
        return None
    scoped = sum(parts.get("jit_train_step", {}).get(p, 0.0)
                 for p in PARTS)
    if not scoped:
        return None
    return 1e3 * (total - scoped) / r.window.steps
