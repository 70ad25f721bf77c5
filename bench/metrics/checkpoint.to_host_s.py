"""Seconds per save spent turning device leaves into host bytes: the summed
``checkpoint.to_host`` spans (``np.asarray`` and ``tobytes`` of each leaf)
over the number of ``checkpoint.upload`` spans in the window."""


def read(run):
    saves = sum(s.name == "checkpoint.upload" for s in run.spans)
    parts = [s.dur for s in run.spans if s.name == "checkpoint.to_host"]
    if not saves or not parts:
        return None
    return sum(parts) / saves
