"""gen_late_ms: 95th percentile of how late the load generator submitted
each request (submit time minus due time). It shows a starved generator,
not a fast server."""
import numpy as np


def read(run):
    late = run.record.get("late_ms")
    if late is None or not len(late):
        return None
    return float(np.percentile(late, 95))
