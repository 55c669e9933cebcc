"""Median request latency, due time to logits on the host (ms)."""
import numpy as np


def read(r):
    return float(np.percentile(r.latencies_s(), 50)) * 1e3
