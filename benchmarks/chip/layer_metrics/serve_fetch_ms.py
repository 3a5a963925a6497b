"""serve_fetch_ms: median ``serve.fetch`` span of the window: the wait for
the device and the copy of the batch's result to the host."""
from benchmarks.chip import scopes


def read(run):
    return scopes.median_span_ms(run, "serve.fetch")
