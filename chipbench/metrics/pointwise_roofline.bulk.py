"""The 1x1 convs' share of their roofline (%): the least time the chip
needs for those convs' work in the window (``work.py``: needed int8
operations at the int8 peak, or needed bytes at the HBM peak, whichever
is longer, per forward) over their kernels' device time
(``configs/resnet_imagenet.layer_kernel_s``)."""
from chipbench.configs.resnet_imagenet import layer_kernel_s


def read(r):
    got = layer_kernel_s(r, 1)
    if got is None or got[0] <= 0:
        return None
    kernel_s, sub = got
    least_s = sum(sub.least_seconds(n, r.peaks)[0] for n in r.forwards())
    return 100.0 * least_s / kernel_s if least_s > 0 else None
