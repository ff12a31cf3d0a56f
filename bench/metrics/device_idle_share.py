"""One minus the union of device operation intervals over the traced
window, averaged over the chips."""


def read(r):
    tr = r.trace
    if tr is None or tr.busy_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
