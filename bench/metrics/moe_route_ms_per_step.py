"""Device time of the train program under the ``moe_route`` scope
(``models/moe.share_ffn``: router logits, sigmoid, top-k, the sort of the
(token, choice) pairs by held expert, the permutations into and out of
the grouped buffer, and the weighted combine), per step."""

import moe_scopes


def read(r):
    return moe_scopes.ms_per(r, "jit_train_step", "moe_route",
                             r.window.steps)
