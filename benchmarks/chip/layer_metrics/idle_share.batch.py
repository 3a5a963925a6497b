"""idle_share.batch: the share of the traced window in which no operation
ran on the device, averaged over chips (batched cells)."""


def read(run):
    return 100.0 * run.trace.idle_share()
