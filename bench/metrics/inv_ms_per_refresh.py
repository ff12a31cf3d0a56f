"""Device time of the inverse refresh program (INV: ``make_inv_refresh``
under the jitted lambda of ``KFACProgram.make_step``) per refresh."""

#: the program's name in the trace: the refresh is an anonymous lambda
MODULE = r"^jit__lambda\b"


def read(r):
    tr = r.trace
    s = tr.module_s(MODULE) if tr is not None else None
    if s is None or not r.window.inv_calls:
        return None
    return 1e3 * s / r.window.inv_calls
