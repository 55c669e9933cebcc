"""Set-up seconds outside the bind: compiling (or loading from the
persistent cache) and running each bucket and each request size once."""


def read(r):
    return r.compile_s
