"""Device time of the Mosaic conv kernels per image served in the traced
window (ms)."""


def read(r):
    n = r.images_served()
    if r.trace is None or not n:
        return None
    return r.trace.kernel_s() / n * 1e3
