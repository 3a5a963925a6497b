"""serve_batch_fill: filled slots over all slots of the batches that ran in
the window (the runtime's telemetry), in percent."""


def read(run):
    if not run.record.get("batches"):
        return None
    return 100.0 * run.record["batch_fill"]
