"""Tokens of all steps in the window over the window's whole time (host
clock, closed on ``block_until_ready``), all chips of the cell."""


def read(r):
    return r.window.tokens / r.window.seconds
