"""Seconds ``TrainSession.restore_model`` took to bring the last aligned
checkpoint back onto the device after the window, host clock."""


def read(run):
    return run.restore_s
