"""serve_exec_ms: median ``serve.execute`` span of the window: one batch on
a worker, from taking it to its last request's result."""
from benchmarks.chip import scopes


def read(run):
    return scopes.median_span_ms(run, "serve.execute")
