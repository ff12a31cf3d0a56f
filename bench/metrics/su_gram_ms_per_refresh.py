"""Device time of the statistics program under the ``soi_gram`` scope
(``core/soi.blocked_gram``: the factor Grams, the in-scan ones of the
activations and the ones of the tap gradients after the pass) per
statistics pass."""

import scopes


def read(r):
    return scopes.ms_per(r, "jit_stats_step", "soi_gram",
                         r.window.stats_calls)
