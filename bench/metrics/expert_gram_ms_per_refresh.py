"""Device time of the statistics program under the ``expert_gram`` scope
(``kernels/grouped_matmul.group_gram``: the held experts' A Grams in the
pass and their G Grams in its backward) per statistics pass."""

import moe_scopes


def read(r):
    return moe_scopes.ms_per(r, "jit_stats_step", "expert_gram",
                             r.window.stats_calls)
