"""serve_dispatch_ms: median ``serve.dispatch`` span of the window: the
executor call (operand to the device, dispatch) up to its async result."""
from benchmarks.chip import scopes


def read(run):
    return scopes.median_span_ms(run, "serve.dispatch")
