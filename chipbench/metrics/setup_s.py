"""Process start to the first timed request (s)."""


def read(r):
    return r.setup_s
