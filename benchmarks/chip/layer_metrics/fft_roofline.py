"""fft_roofline: the least time a chip needs for its share of the
transform's own work (fixed by batch, N and dtype; see work.py) over the
device time per call of every operation the transform ran, whatever
implements it. HBM bounds it at every cell's size."""
import sys

from benchmarks.chip import work


def read(run):
    r = run.record
    ns = run.trace.op_ns()
    if ns == 0:
        return None
    w = work.transform_work(r["b"], r["n"], r["itemsize"])
    t, bound = work.least_time(w, run.peaks, r["chips"])
    print(f"fft_roofline: least time {t * 1e3:.4f} ms a call per chip, "
          f"bound by {bound}", file=sys.stderr)
    return work.roofline_pct(w, run.peaks, ns / r["calls"] / 1e9, r["chips"])
