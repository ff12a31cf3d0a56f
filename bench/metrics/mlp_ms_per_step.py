"""Device time of the train program under the ``mlp`` scope, which names
the MLP (``models/lm._mlp_block``: gate, up and down projections
and the activation, forward, recomputed forward and backward), per step."""

import scopes


def read(r):
    return scopes.ms_per(r, "jit_train_step", "mlp", r.window.steps)
