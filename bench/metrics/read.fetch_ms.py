"""Staging-thread fetch time per staged batch, in ms: the summed durations
of the window's ``pipeline.stage.fetch`` spans (the fan-in of every rank's
read, timed-out polls included) over the batches staged in the window."""


def read(run):
    spans = [s for s in run.spans if s.name == "pipeline.stage.fetch"]
    if not spans or not run.staged_batches:
        return None
    return 1e3 * sum(s.dur for s in spans) / run.staged_batches
