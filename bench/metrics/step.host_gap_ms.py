"""The host's side of each per-step device idle gap, in ms: the median over
the window's consecutive steps (i, i+1) of the time from the end of step
i's ``pipeline.sync`` span (its loss on the host) to the end of step i+1's
``pipeline.dispatch`` span (the next step enqueued). Pairs with a
``checkpoint``-category span between them are left out."""
import statistics


def read(run):
    def ends(name):
        return {s.args["step"]: s.t0 + s.dur for s in run.spans
                if s.name == name}
    synced, dispatched = ends("pipeline.sync"), ends("pipeline.dispatch")
    saves = [(s.t0, s.t0 + s.dur) for s in run.spans
             if getattr(s, "cat", "") == "checkpoint"]
    gaps = [dispatched[i + 1] - end for i, end in synced.items()
            if i + 1 in dispatched
            and not any(a < dispatched[i + 1] and b > end for a, b in saves)]
    if not gaps:
        return None
    return 1e3 * statistics.median(gaps)
