"""Device time of the train program under the ``attn`` scope, which names
attention (``models/lm._attn_block``: the QKV and O projections, rotary,
scores and softmax, forward, recomputed forward and backward), per step."""

import scopes


def read(r):
    return scopes.ms_per(r, "jit_train_step", "attn", r.window.steps)
