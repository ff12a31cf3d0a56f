"""Device time of the train program under the ``mla`` scope
(``models/lm._mla_block``: the q, kv_a, kv_b and o projections, the
latent norm, rotary and the attention kernel, forward, recomputed forward
and backward), per step."""

import moe_scopes


def read(r):
    return moe_scopes.ms_per(r, "jit_train_step", "mla", r.window.steps)
