"""abft_overhead: device time per call of the ABFT executor over that of
the plain executor on the same input, minus 1, in percent (the traced run
of an ABFT cell times both)."""
from benchmarks.chip.trace import PLAIN


def read(run):
    if not run.record.get("plain") or PLAIN not in run.trace.windows:
        return None
    ft = run.trace.op_ns() / run.record["calls"]
    plain = run.trace.op_ns(PLAIN) / run.record["plain_calls"]
    if plain == 0:
        return None
    return 100.0 * (ft / plain - 1.0)
