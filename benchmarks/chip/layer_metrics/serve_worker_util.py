"""serve_worker_util: the workers' busy share, in percent: the summed
``serve.execute`` spans that start in the window over the number of
workers times the window."""
from benchmarks.chip import scopes


def read(run):
    busy = scopes.spans(run, "serve.execute")
    if not busy:
        return None
    lo, hi = run.trace.span()
    workers = run.ctx.config["runtime"]["workers"]
    return 100.0 * sum(e - s for s, e in busy) / (workers * (hi - lo))
