"""Process start to the first timed step: imports, building and compiling
(or loading from the cache) every program, weights, the first steps."""


def read(r):
    return r.setup_s
