"""Images answered per second: every request sent inside the window,
over the time from the window's start to its last answer."""


def read(r):
    return r.images_per_s()
