"""mesh_scope_cover: the share of chip 0's window device time whose op maps
to a program scope, in percent, in a cell on several chips; prints each
scope's time per call, the largest unscoped ops and the collectives a
call."""
from benchmarks.chip import mesh_scopes


def read(run):
    ns = mesh_scopes.scope_ns(run, 0)
    total = sum(ns.values()) if ns is not None else 0
    if not total:
        return None
    mesh_scopes.report(run)
    return 100.0 * (total - ns[None]) / total
