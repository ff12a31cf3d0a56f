"""Device time of the train program under the ``moe_experts`` scope (the
held experts' grouped gate, up and down matmuls and the activation,
forward, recomputed forward and backward), per step."""

import moe_scopes


def read(r):
    return moe_scopes.ms_per(r, "jit_train_step", "moe_experts",
                             r.window.steps)
