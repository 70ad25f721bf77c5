"""Device time of one run of the step program, in ms: the traced window's
``XLA Modules`` events of ``train_step`` over how many ran."""


def read(run):
    if run.trace is None or not run.trace["step_programs"]:
        return None
    return 1e3 * run.trace["step_device_s"] / run.trace["step_programs"]
