"""Seconds per save the trainer waits for leaf PUTs, after its last
device-to-host copy and whenever the copies held by unfinished PUTs reach
the program's bound: the summed ``checkpoint.drain`` spans over the number
of ``checkpoint.upload`` spans in the window. None where there is no save,
or no drain span (a program that PUTs each leaf before the next copy)."""


def read(run):
    saves = sum(s.name == "checkpoint.upload" for s in run.spans)
    parts = [s.dur for s in run.spans if s.name == "checkpoint.drain"]
    if not saves or not parts:
        return None
    return sum(parts) / saves
