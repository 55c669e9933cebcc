"""Device time of every op that is not a Mosaic kernel, per image served
in the traced window (ms)."""


def read(r):
    n = r.images_served()
    if r.trace is None or not n:
        return None
    return r.trace.glue_s() / n * 1e3
