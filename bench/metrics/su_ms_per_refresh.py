"""Device time of the statistics program (SU: ``make_stats_step``) per
statistics pass."""

#: the program's name in the trace
MODULE = r"^jit_stats_step\b"


def read(r):
    tr = r.trace
    s = tr.module_s(MODULE) if tr is not None else None
    if s is None or not r.window.stats_calls:
        return None
    return 1e3 * s / r.window.stats_calls
