"""Traffic drivers, one module per kind of traffic, named by a traffic file's
``driver`` key. Each defines ``Cell(ctx)`` with ``window(seconds)``,
``check(control=None)`` and ``close()``."""
