"""Device time of the train program under the ``head`` scope, which names
the head (``models/lm._logits`` and ``loss_from_logits``: the vocabulary
projection and the cross-entropy, forward and backward), per step."""

import scopes


def read(r):
    return scopes.ms_per(r, "jit_train_step", "head", r.window.steps)
