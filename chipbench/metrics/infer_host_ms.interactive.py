"""Mean per ``infer`` call of its span time with no device op running
(ms): host work in ``CnnServer.infer`` that the device does not hide."""
import numpy as np


def read(r):
    if r.trace is None or not r.infer_spans():
        return None
    return float(np.mean(r.trace.uncovered_s("infer"))) * 1e3
