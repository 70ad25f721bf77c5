"""Seconds per save spent in the store's PUTs: the summed ``checkpoint.put``
spans (each leaf and the ``MANIFEST.ckpt``) over the number of
``checkpoint.upload`` spans in the window."""


def read(run):
    saves = sum(s.name == "checkpoint.upload" for s in run.spans)
    parts = [s.dur for s in run.spans if s.name == "checkpoint.put"]
    if not saves or not parts:
        return None
    return sum(parts) / saves
