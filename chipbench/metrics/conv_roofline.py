"""The conv kernels' share of their roofline (%): the least time the
chip needs for the window's conv work (``work.py``: needed int8
operations at the int8 peak, or needed bytes at the HBM peak, whichever
is longer, per forward) over the kernels' device time."""


def read(r):
    if r.trace is None:
        return None
    kernel_s = r.trace.kernel_s()
    least_s, _ = r.conv_least_s()
    if kernel_s <= 0 or least_s <= 0:
        return None
    return 100.0 * least_s / kernel_s
