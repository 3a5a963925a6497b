"""glue_ms: device time per call of the transform's operations outside its
Pallas kernels (re/im split and join, layout copies, the ABFT decode and
correction), averaged over chips."""


def read(run):
    names = set(run.ctx.config.get("kernels", ()))
    if not names:
        return None
    ns = run.trace.op_ns(pred=lambda o: o.base not in names)
    return ns / run.record["calls"] / 1e6
