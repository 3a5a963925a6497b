"""idle_share.serve: the share of the traced window in which no operation
ran on the device (serving cells)."""


def read(run):
    return 100.0 * run.trace.idle_share()
