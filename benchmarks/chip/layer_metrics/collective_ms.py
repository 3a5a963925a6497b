"""collective_ms: device time per call of the collective operations
(all-to-all, all-gather, all-reduce, ...) on chip 0."""
from benchmarks.chip.trace import is_collective


def read(run):
    ns = sum(o.end - o.start for o in run.trace.ops_in(0)
             if is_collective(o))
    if ns == 0:
        return None
    return ns / run.record["calls"] / 1e6
