"""scope_cover: the share of the window's device time on chip 0 whose op
maps to a program scope, in percent; prints each scope's time per call and
the largest unscoped ops."""
from benchmarks.chip import scopes


def read(run):
    ns = scopes.scope_ns(run)
    total = sum(ns.values()) if ns is not None else 0
    if not total:
        return None
    scopes.report(run)
    return 100.0 * (total - ns[None]) / total
