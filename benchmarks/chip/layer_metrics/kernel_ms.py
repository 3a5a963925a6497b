"""kernel_ms: device time per call of the configuration's Pallas kernels
(``block_fft``, ``abft_fft``) in the traced window, averaged over chips."""


def read(run):
    names = set(run.ctx.config.get("kernels", ()))
    ns = run.trace.op_ns(pred=lambda o: o.base in names)
    if not names or ns == 0:
        return None
    return ns / run.record["calls"] / 1e6
