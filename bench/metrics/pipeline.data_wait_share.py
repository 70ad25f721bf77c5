"""Share of the window the trainer spent waiting for a staged batch, in %:
the sum of ``StepTiming.data_wait_s`` over the window's steps, over the
window's wall time."""


def read(run):
    if not run.reports or run.window_s <= 0:
        return None
    wait = sum(t.data_wait_s for r in run.reports for t in r.timings)
    return 100.0 * wait / run.window_s
