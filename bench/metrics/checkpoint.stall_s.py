"""Mean seconds the trainer stood still per save: host clock around each
``FusedTrainLoop.aligned_checkpoint`` call of the window."""


def read(run):
    if not run.stalls_s:
        return None
    return sum(run.stalls_s) / len(run.stalls_s)
