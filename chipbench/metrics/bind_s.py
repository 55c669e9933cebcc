"""Seconds in the server's install (fold, mask fingerprint) and in
``cnn.bind_execution``, host clock."""


def read(r):
    return r.bind_s
