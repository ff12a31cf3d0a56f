"""Device time of the train program (forward, backward and the K-FAC
update, ``launch/steps.make_train_step``) per step."""

#: the program's name in the trace
MODULE = r"^jit_train_step\b"


def read(r):
    tr = r.trace
    s = tr.module_s(MODULE) if tr is not None else None
    if s is None or not r.window.steps:
        return None
    return 1e3 * s / r.window.steps
