"""serve_p95_ms: the 95th percentile of all the window's request latencies,
each from when it was due (the serving driver's own reading). Host stalls
of half a second or more swing it from run to run on one chip, so it
stands here, unbounded, beside the bounded median."""


def read(run):
    return run.record["e2e"].get("serve_p95_ms")
