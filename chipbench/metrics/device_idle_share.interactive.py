"""Share of the traced window with no op on the device (%)."""


def read(r):
    return r.idle_share_pct()
