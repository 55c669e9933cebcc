"""Needed int8 operations per image x images per second, over the
chip's int8 peak (%): the whole forward's share of the peak."""


def read(r):
    return 100.0 * r.work.ops_per_image * r.images_per_s() / r.peaks["int8_ops"]
