"""Device idle time whose gap began under the ``phase:sync`` span (the
host's wait on the step counter in ``launch/train.step_fn``, or the SMW
drift gate) per step, from the trace's idle-gap attribution."""

#: the span's name in the trace
SPAN = "phase:sync"


def read(r):
    tr = r.trace
    if tr is None or not r.window.steps:
        return None
    s = dict(tr.idle_gaps).get(SPAN)
    if s is None:
        return None
    return 1e3 * s / r.window.steps
