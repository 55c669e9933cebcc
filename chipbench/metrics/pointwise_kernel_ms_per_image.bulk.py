"""Device time of the Mosaic kernels of the 1x1 convs per image served
in the traced window (ms). The convs are those the run's needed work
records at kernel size 1; an op is theirs by its layer name
(``configs/resnet_imagenet.layer_kernel_s``)."""
from chipbench.configs.resnet_imagenet import layer_kernel_s


def read(r):
    n, got = r.images_served(), layer_kernel_s(r, 1)
    if got is None or not n or got[0] <= 0:
        return None
    return got[0] / n * 1e3
