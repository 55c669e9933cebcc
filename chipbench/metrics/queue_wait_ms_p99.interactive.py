"""99th percentile of due time to the handoff to ``infer`` (ms): the
wait in ``BucketBatcher`` and behind earlier batches."""
import numpy as np


def read(r):
    return float(np.percentile(r.queue_waits_s(), 99)) * 1e3
