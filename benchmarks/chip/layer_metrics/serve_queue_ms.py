"""serve_queue_ms: median time a window's request waited for its batch to
close (``RequestHandle.queue_s``: batched minus submitted)."""
import numpy as np


def read(run):
    q = run.record.get("queue_ms")
    if q is None or not len(q):
        return None
    return float(np.median(q))
