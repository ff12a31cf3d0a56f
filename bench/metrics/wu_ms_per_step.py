"""Device time of the train program under the ``wu`` scope, which names
WU (``core/kfac.apply_updates``: the K-FAC precondition, momentum,
Adam and the parameter update), per step."""

import scopes


def read(r):
    return scopes.ms_per(r, "jit_train_step", "wu", r.window.steps)
